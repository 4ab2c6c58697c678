"""Synapse groups: connectivity + representation + generated dynamics.

A SynapseGroup connects a pre to a post population.  Representation is chosen
per the paper's memory model (eqs. (1)/(2)) unless forced.  Dynamics are no
longer hardcoded branches: every group carries a GeNN-style

  - WeightUpdateModel  (what a presynaptic spike contributes, plus optional
                        trace-based learning updating ``g`` online), and
  - PostsynapticModel  (how arriving input decays and is applied to the post
                        neuron, with an optional reversal-potential term),

both declared as code snippets and compiled through the same AST-whitelist ->
jit pipeline as neuron models (repro.core.codegen).  The built-ins `Pulse`,
`ExpDecay`, `ExpCond` reproduce the historical 'pulse'/'exp_decay' branches;
`Alpha` and `STDP` are only expressible through the generated path.

`gscale` is the paper's synaptic-conductance scaling factor — the quantity
the whole scalability study is about.  It multiplies the stored conductances
at propagation time so a single network build can be swept over gscale.

Dendritic delays (GeNN's per-synapse delay model): every group may carry an
integer delay per synapse (``ELLSynapses.delay``) or a homogeneous
``delay_steps``; both land weighted currents in a post-side ring
``[max_delay+1, n_post]`` (``SynapseState.dendritic``) read at the cursor —
post-sized state that shards along the post axis, replacing the old
replicated pre-side spike ring.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codegen import (CompiledWeightUpdate, PostsynapticModel,
                                WeightUpdateModel, compile_postsynaptic,
                                compile_weight_update)
from repro.core.snn.errors import SpecError
from repro.sparse import formats as F
from repro.sparse import ops as sparse_ops
from repro.kernels import autotune as AT
from repro.kernels import ops as kops

__all__ = [
    "SynapseGroup", "SynapseState", "LocalConnectivity", "make_group",
    "Pulse", "ExpDecay", "ExpCond", "Alpha",
    "StaticPulse", "STDP", "PROPAGATIONS",
]

PROPAGATIONS = ("auto", "dense", "event")


# ---------------------------------------------------------------------------
# Built-in postsynaptic models.  Pulse/ExpDecay/ExpCond reproduce the
# pre-redesign hardcoded branches bit-for-bit (same operations in the same
# order, dt and tau entering as python floats).
# ---------------------------------------------------------------------------

def Pulse() -> PostsynapticModel:
    """Instantaneous current injection (the Izhikevich cortical net)."""
    return PostsynapticModel(name="pulse")


def ExpDecay(tau_ms: float) -> PostsynapticModel:
    """Exponentially decaying current, time constant tau_ms."""
    return PostsynapticModel(
        name="exp_decay",
        state={"in_syn": 0.0},
        params={"tau": float(tau_ms)},
        decay_code="in_syn = in_syn * exp(-dt / tau) + inj",
        apply_code="in_syn",
    )


def ExpCond(tau_ms: float, e_rev: float) -> PostsynapticModel:
    """Exponentially decaying conductance with reversal potential e_rev."""
    return PostsynapticModel(
        name="exp_cond",
        state={"in_syn": 0.0},
        params={"tau": float(tau_ms), "e_rev": float(e_rev)},
        decay_code="in_syn = in_syn * exp(-dt / tau) + inj",
        apply_code="in_syn * (e_rev - V)",
    )


def Alpha(tau_ms: float) -> PostsynapticModel:
    """Alpha-function synapse x(t) ~ (t/tau) exp(-t/tau) — a two-stage
    exponential cascade the old 'pulse'/'exp_decay' API could not express."""
    return PostsynapticModel(
        name="alpha",
        state={"x": 0.0, "y": 0.0},
        params={"tau": float(tau_ms)},
        decay_code=(
            "x = (x + (dt / tau) * y) * exp(-dt / tau)\n"
            "y = y * exp(-dt / tau) + inj"
        ),
        apply_code="x",
    )


# ---------------------------------------------------------------------------
# Built-in weight-update models.
# ---------------------------------------------------------------------------

def StaticPulse() -> WeightUpdateModel:
    """A spike contributes the stored conductance g; no learning."""
    return WeightUpdateModel(name="static_pulse")


def STDP(lr: float = 0.005, tau_pre: float = 20.0, tau_post: float = 20.0,
         g_min: float = 0.0, g_max: float = 1.0) -> WeightUpdateModel:
    """Trace-based pair STDP updating ``g`` online from pre/post spike
    coincidence — potentiation when pre precedes post, depression when post
    precedes pre.  Not expressible in the pre-redesign API."""
    return WeightUpdateModel(
        name="stdp",
        params={"lr": float(lr), "tau_pre": float(tau_pre),
                "tau_post": float(tau_post), "g_min": float(g_min),
                "g_max": float(g_max)},
        pre_state={"x_pre": 0.0},
        post_state={"x_post": 0.0},
        pre_code="x_pre = x_pre * exp(-dt / tau_pre) + pre_spike",
        post_code="x_post = x_post * exp(-dt / tau_post) + post_spike",
        learn_code=("g = clip(g + lr * x_pre * post_spike"
                    " - lr * x_post * pre_spike, g_min, g_max)"),
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SynapseState:
    """Per-group dynamic state (pytree).

    ``dendritic`` is the post-side dendritic-delay ring
    [max_delay+1, n_post]: arriving weighted currents are scatter-added
    ``delay`` slots ahead of the cursor and delivered when the cursor
    reaches them.  It replaces the old pre-side spike ring
    ([delay+1, n_pre]) — post-sized state shards along the post/neuron
    axis, so no per-group buffer is replicated across devices.
    """

    psm: Dict[str, jax.Array]          # postsynaptic model state   [n_post]
    wu_pre: Dict[str, jax.Array]       # presynaptic trace vars     [n_pre]
    wu_post: Dict[str, jax.Array]      # postsynaptic trace vars    [n_post]
    g: Optional[jax.Array]             # dynamic weights (plastic groups)
    syn: Dict[str, jax.Array]          # extra per-synapse vars [n_pre, K]
    dendritic: Optional[jax.Array]     # delay ring [max_delay+1, n_post]
    cursor: Optional[jax.Array]        # ring cursor, int32 scalar

    @property
    def in_syn(self) -> Optional[jax.Array]:
        """Legacy accessor for the ExpDecay/ExpCond conductance state."""
        return self.psm.get("in_syn")

    def tree_flatten(self):
        return (self.psm, self.wu_pre, self.wu_post, self.g, self.syn,
                self.dendritic, self.cursor), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclasses.dataclass(frozen=True)
class LocalConnectivity:
    """A step-time connectivity override: the sharded engine passes each
    device's post-shard of the group's connectivity through
    ``SynapseGroup.step(conn=...)`` while reusing the group's compiled
    dynamics unchanged.  Replaces the deprecated ``ell=``/``dense=`` kwarg
    pair (one declared object instead of two loose knobs)."""

    ell: F.ELLSynapses
    dense: Optional[jax.Array] = None


@dataclasses.dataclass
class SynapseGroup:
    name: str
    pre: str
    post: str
    ell: F.ELLSynapses                      # canonical storage
    dense: Optional[jax.Array] = None       # dense mirror when chosen/forced
    representation: str = "auto"            # 'auto' | 'sparse' | 'dense'
    propagation: str = "auto"               # 'auto' | 'dense' | 'event'
    wum: Optional[WeightUpdateModel] = None  # default StaticPulse()
    psm: Optional[PostsynapticModel] = None  # default from legacy `dynamics`
    delay_steps: int = 0                    # homogeneous dendritic delay
    max_delay: Optional[int] = None         # static ring bound for ell.delay
    sign: float = 1.0                       # +1 excitatory / -1 inhibitory
    # a custom update writes g: conductances become state-resident even
    # without a learning rule (ModelSpec.build sets this)
    mutable_g: bool = False
    # legacy shorthand (pre-ModelSpec API); translated to a PostsynapticModel
    # in __post_init__ and kept for introspection.
    dynamics: Optional[str] = None          # 'pulse' | 'exp_decay'
    tau_ms: float = 5.0                     # for exp_decay
    e_rev: Optional[float] = None           # reversal potential (cond-based)

    def __post_init__(self) -> None:
        if self.psm is None:
            dyn = self.dynamics or "pulse"
            if dyn == "pulse":
                self.psm = Pulse()
            elif dyn == "exp_decay":
                self.psm = (ExpDecay(self.tau_ms) if self.e_rev is None
                            else ExpCond(self.tau_ms, self.e_rev))
            else:
                raise ValueError(
                    f"{self.name}: unknown dynamics {dyn!r} "
                    "(expected 'pulse' or 'exp_decay', or pass psm=)")
        self.dynamics = self.psm.name
        if self.wum is None:
            self.wum = StaticPulse()

        # --- dendritic delays ------------------------------------------
        # delay_steps=k (homogeneous) and ell.delay (per-synapse slot) both
        # lower onto the same post-side dendritic ring; the homogeneous case
        # keeps the single full-matrix spmv per step (one ring slot written).
        if self.propagation not in PROPAGATIONS:
            raise ValueError(
                f"synapse group {self.name!r}: propagation "
                f"{self.propagation!r} not in {PROPAGATIONS}")
        if self.propagation == "event":
            if self.representation == "dense":
                raise ValueError(
                    f"synapse group {self.name!r}: propagation='event' is "
                    "incompatible with representation='dense' (event-driven "
                    "delivery gathers the spiking pre-neurons' ELL rows); "
                    "use representation 'sparse' or 'auto'")
            self.representation = "sparse"

        if not isinstance(self.delay_steps, int) or self.delay_steps < 0:
            raise ValueError(
                f"{self.name}: delay_steps must be a non-negative int, got "
                f"{self.delay_steps!r}")
        if self.ell.delay is not None:
            if self.delay_steps:
                raise ValueError(
                    f"{self.name}: delay_steps={self.delay_steps} and a "
                    "per-synapse delay slot are mutually exclusive; declare "
                    "one of them")
            if tuple(self.ell.delay.shape) != tuple(self.ell.g.shape):
                raise ValueError(
                    f"{self.name}: delay slot shape "
                    f"{tuple(self.ell.delay.shape)} != synapse shape "
                    f"{tuple(self.ell.g.shape)}")
            if self.representation == "dense":
                raise ValueError(
                    f"synapse group {self.name!r}: representation='dense' "
                    "is incompatible with per-synapse delays (the dense "
                    "mirror has no delay slot; currents route through the "
                    "ELL path); use 'sparse' or 'auto'")
            self.representation = "sparse"
            dvals = np.asarray(jax.device_get(self.ell.delay))
            dmax = int(dvals.max()) if dvals.size else 0
            if dvals.size and int(dvals.min()) < 0:
                raise ValueError(
                    f"{self.name}: negative per-synapse delay "
                    f"{int(dvals.min())}")
            if self.max_delay is None:
                self.max_delay = dmax
            elif dmax > self.max_delay:
                raise ValueError(
                    f"{self.name}: per-synapse delay {dmax} exceeds the "
                    f"declared ring bound max_delay={self.max_delay}")
        else:
            self.max_delay = self.delay_steps

        # Any non-default weight-update model — or a custom update writing
        # g — propagates through the ELL effective-weight path (plastic /
        # mutable g lives in state; custom spike_code rewrites weights per
        # step), so a dense mirror would go stale or sit unused: an
        # explicit 'dense' request is a conflict, and 'auto' resolves to
        # sparse.
        if not self.wum.is_static_pulse or self.mutable_g:
            if self.representation == "dense":
                what = ("a custom update writing g" if self.mutable_g
                        and self.wum.is_static_pulse
                        else f"weight-update model {self.wum.name!r}")
                raise ValueError(
                    f"synapse group {self.name!r}: representation='dense' "
                    f"is incompatible with {what} (dynamic weights "
                    "propagate via the ELL path); use 'sparse' or 'auto'")
            self.representation = "sparse"
        elif self.representation == "auto":
            nnz = self.ell.n_pre * self.ell.max_conn
            self.representation = F.choose_representation(
                self.ell.n_pre, self.ell.n_post, nnz)
        if self.representation == "dense" and self.dense is None:
            self.dense = F.ell_to_dense(self.ell)

        # --- propagation mode (declared -> effective) -------------------
        # 'auto' asks the occupancy/activity crossover model whether event-
        # driven row gathering beats the full-matrix pass for this group's
        # shape; an explicit 'event' keeps the modelled capacity but skips
        # the verdict.  Both paths are bit-exact, so the choice is purely a
        # performance decision.
        self.propagation_declared = self.propagation
        if self.representation == "dense" or self.propagation == "dense":
            self.propagation_mode = "dense"
            self.event_capacity = None
        else:
            cfg = AT.choose_propagation(
                self.ell.n_pre, self.ell.max_conn, self.ell.n_post,
                n_slots=(self.ring_slots if self.ell.delay is not None
                         else 1),
                tag=self.name)
            if self.propagation == "event":
                self.propagation_mode = "event"
            else:
                self.propagation_mode = cfg["mode"]
            self.event_capacity = (int(cfg["capacity"])
                                   if self.propagation_mode == "event"
                                   else None)

        # --- code generation: compile the synapse models once per group ---
        self._psm_step = compile_postsynaptic(self.psm)
        self._wu: CompiledWeightUpdate = compile_weight_update(self.wum)

    @property
    def plastic(self) -> bool:
        """True when g is state-resident: a learn_code rewrites it during
        simulation, or a custom update may rewrite it on demand."""
        return bool(self.wum.learn_code) or self.mutable_g

    @property
    def needs_ring(self) -> bool:
        """True when this group carries a dendritic-delay ring (homogeneous
        delay_steps > 0 or a per-synapse delay slot, even an all-zero one)."""
        return self.max_delay > 0 or self.ell.delay is not None

    @property
    def ring_slots(self) -> int:
        return self.max_delay + 1

    # -- state ------------------------------------------------------------
    def init_state(self) -> SynapseState:
        n_pre, n_post = self.ell.n_pre, self.ell.n_post
        psm = {k: jnp.full((n_post,), v, jnp.float32)
               for k, v in self.psm.state.items()}
        wu_pre = {k: jnp.full((n_pre,), v, jnp.float32)
                  for k, v in self.wum.pre_state.items()}
        wu_post = {k: jnp.full((n_post,), v, jnp.float32)
                   for k, v in self.wum.post_state.items()}
        syn = {k: jnp.full((n_pre, self.ell.max_conn), v, jnp.float32)
               for k, v in self.wum.syn_state.items()}
        g = jnp.asarray(self.ell.g) if self.plastic else None
        if self.needs_ring:
            buf = jnp.zeros((self.ring_slots, n_post), jnp.float32)
            cur = jnp.zeros((), jnp.int32)
        else:
            buf, cur = None, None
        return SynapseState(psm=psm, wu_pre=wu_pre, wu_post=wu_post, g=g,
                            syn=syn, dendritic=buf, cursor=cur)

    # -- propagation -------------------------------------------------------
    def _effective_ell(self, g: Optional[jax.Array],
                       syn: Dict[str, jax.Array],
                       externals: Dict[str, jax.Array],
                       ell: F.ELLSynapses) -> F.ELLSynapses:
        """The ELL matrix to propagate this step: the stored one for static
        groups, or one carrying this step's effective weights (computed
        ONCE per step — the old masked-pass delay loop recomputed them per
        delay value)."""
        if self.wum.is_static_pulse and g is None:
            return ell
        g_cur = ell.g if g is None else g
        w_eff = self._wu.effective_weight(g_cur, syn, self.wum.params,
                                          externals)
        w_eff = jnp.where(ell.valid, w_eff, 0.0)
        return F.ELLSynapses(g=w_eff, post_ind=ell.post_ind, valid=ell.valid,
                             n_post=ell.n_post, delay=ell.delay)

    def _spmv(self, ell: F.ELLSynapses, spk: jax.Array) -> jax.Array:
        """One full accumulation via the group's effective propagation mode
        (dense full-matrix pass vs event-driven row gathering)."""
        if self.propagation_mode == "event":
            return kops.ell_spmv_event(ell, spk, self.event_capacity)
        with jax.named_scope("dense"):
            return kops.ell_spmv(ell, spk)

    def _raw_current(self, spikes: jax.Array, gscale: jax.Array,
                     g: Optional[jax.Array], syn: Dict[str, jax.Array],
                     externals: Dict[str, jax.Array],
                     ell: F.ELLSynapses,
                     dense: Optional[jax.Array]) -> jax.Array:
        """sum_i spike_i * w_eff_ij * gscale for this step's arriving
        spikes.  `ell`/`dense` are the resolved (possibly shard-local)
        connectivity."""
        spk = jnp.asarray(spikes, jnp.float32)
        if (self.wum.is_static_pulse and g is None
                and self.representation == "dense"):
            with jax.named_scope("dense"):
                out = sparse_ops.accumulate_dense(dense, spk)
        else:
            out = self._spmv(self._effective_ell(g, syn, externals, ell), spk)
        return self.sign * gscale * out

    def _delay_contrib(self, spikes: jax.Array, gscale: jax.Array,
                       g: Optional[jax.Array], syn: Dict[str, jax.Array],
                       externals: Dict[str, jax.Array],
                       ell: F.ELLSynapses) -> jax.Array:
        """Fused heterogeneous-delay accumulation: one pass over the ELL
        slots returns [ring_slots, n_post] — slot d holds the currents due
        d steps from now.  Replaces the max_delay+1 masked spmv passes;
        per (slot, post) the accumulation order is unchanged, so the ring
        contents stay bit-exact."""
        spk = jnp.asarray(spikes, jnp.float32)
        eff = self._effective_ell(g, syn, externals, ell)
        if self.propagation_mode == "event":
            out = kops.ell_spmv_event_delay(eff, spk, self.ring_slots,
                                            self.event_capacity)
        else:
            with jax.named_scope("dense"):
                out = kops.ell_spmv_delay(eff, spk, self.ring_slots)
        return self.sign * gscale * out

    def _resolve_conn(self, conn: Optional[LocalConnectivity],
                      ell: Optional[F.ELLSynapses],
                      dense: Optional[jax.Array]) -> LocalConnectivity:
        """Fold the step-time overrides into one LocalConnectivity.  The
        loose ``ell=``/``dense=`` kwargs are deprecated in favor of
        ``conn=``; passing both is a conflict."""
        if ell is not None or dense is not None:
            if conn is not None:
                raise SpecError(
                    f"synapse group {self.name!r}: conn= and the deprecated "
                    "ell=/dense= overrides were both passed to step() and "
                    "conflict; pass only conn=LocalConnectivity(...)")
            warnings.warn(
                "SynapseGroup.step(ell=..., dense=...) is deprecated; pass "
                "conn=LocalConnectivity(ell=..., dense=...) instead "
                "(docs/API.md 'Propagation modes' has the migration table)",
                DeprecationWarning, stacklevel=3)
            return LocalConnectivity(
                ell=ell if ell is not None else self.ell,
                dense=dense if dense is not None else self.dense)
        if conn is None:
            return LocalConnectivity(ell=self.ell, dense=self.dense)
        return conn

    def step(
        self, state: SynapseState, spikes: jax.Array, gscale: jax.Array,
        dt: float, v_post: Optional[jax.Array] = None,
        post_spikes: Optional[jax.Array] = None,
        t: Optional[jax.Array] = None,
        conn: Optional[LocalConnectivity] = None,
        ell: Optional[F.ELLSynapses] = None,
        dense: Optional[jax.Array] = None,
        pre_traces: Optional[Dict[str, jax.Array]] = None,
    ) -> tuple[SynapseState, jax.Array]:
        """Advance one step; returns (new_state, current into post neurons).

        `conn` overrides the stored connectivity (sharded engine path); all
        shapes on the post side then follow the override.  The loose
        ``ell=``/``dense=`` kwargs are a deprecated spelling of the same
        override (DeprecationWarning; conflicting with conn= raises
        SpecError).

        `pre_traces`: when not None, the caller owns the pre-trace state —
        the internal pre_step is skipped (state.wu_pre passes through
        untouched; the sharded engine advances its own pre-sharded copy) and
        learn reads these full-size [n_pre] trace vectors instead.  The
        host path always passes None.

        Dendritic delays: each synapse's weighted contribution is scatter-
        added into the post-side ring ``delay`` slots ahead of the cursor
        and delivered when the cursor reaches it.  The homogeneous
        ``delay_steps=k`` case writes one ring slot with the same single
        full-matrix accumulation as the delay-free path; heterogeneous
        per-synapse delays run ONE fused delay-scatter pass that lands every
        synapse's contribution at its (delay_slot, post) ring coordinate
        (kernels.ops.ell_spmv_delay — bit-exact vs the old max_delay+1
        masked passes, one kernel launch instead of S).
        Weights (and gscale) are applied at *spike* time, GeNN's dendritic-
        delay semantics — for plastic groups this reads g as of emission,
        not delivery (the migration note in docs/API.md spells this out).
        """
        conn = self._resolve_conn(conn, ell, dense)
        lell = conn.ell
        # dt/t are always present in the snippet environments: any model
        # code referencing them must work even when a legacy caller omits t
        wu_ext = {"dt": dt, "t": t if t is not None else jnp.float32(0.0)}
        # the per-synapse delay slot is readable from spike_code/learn_code;
        # homogeneous groups see their scalar delay_steps (keeping
        # ConstantDelay(k) == delay_steps=k for delay-reading snippets) and
        # delay-free groups see 0.0, so snippets stay portable
        wu_ext["delay"] = (lell.delay.astype(jnp.float32)
                           if lell.delay is not None
                           else jnp.float32(self.delay_steps))

        if not self.needs_ring:
            inj = self._raw_current(spikes, gscale, state.g, state.syn,
                                    wu_ext, lell, conn.dense)
            new_buf, new_cur = state.dendritic, state.cursor
        else:
            S = self.ring_slots
            cur = state.cursor
            ring = state.dendritic
            if lell.delay is None:
                # homogeneous: one full accumulation, one slot written
                contrib = self._raw_current(spikes, gscale, state.g,
                                            state.syn, wu_ext, lell,
                                            conn.dense)
                ring = ring.at[(cur + self.delay_steps) % S].add(contrib)
            else:
                # fused delay scatter: contrib_all[d] is what the old d-th
                # masked pass produced; rolling by the cursor aligns slot d
                # with ring row (cur+d) % S, one add per slot as before
                contrib_all = self._delay_contrib(spikes, gscale, state.g,
                                                  state.syn, wu_ext, lell)
                ring = ring + jnp.roll(contrib_all, cur, axis=0)
            inj = ring[cur]
            new_buf = ring.at[cur].set(0.0)
            new_cur = (cur + 1) % S

        # -- learning (generated weight-update code) -----------------------
        # pre traces and learning fire at spike (emission) time — the
        # dendritic delay buffers the *current*, not the spike event
        pre_spk = jnp.asarray(spikes, jnp.float32)
        post_spk = (jnp.asarray(post_spikes, jnp.float32)
                    if post_spikes is not None
                    else jnp.zeros((lell.n_post,), jnp.float32))
        new_pre = state.wu_pre
        if pre_traces is None and self._wu.pre_step is not None:
            new_pre = self._wu.pre_step(
                state.wu_pre, self.wum.params,
                {**wu_ext, "pre_spike": pre_spk})
        new_post = state.wu_post
        if self._wu.post_step is not None:
            new_post = self._wu.post_step(
                state.wu_post, self.wum.params,
                {**wu_ext, "post_spike": post_spk})
        new_g, new_syn = state.g, state.syn
        if self._wu.learn is not None:
            gather = lell.post_ind
            traces = {"pre_spike": pre_spk[:, None],
                      "post_spike": post_spk[gather]}
            pre_read = new_pre if pre_traces is None else pre_traces
            traces.update({k: v[:, None] for k, v in pre_read.items()})
            traces.update({k: v[gather] for k, v in new_post.items()})
            g_learn, new_syn = self._wu.learn(
                state.g, state.syn, traces, self.wum.params, wu_ext)
            new_g = jnp.where(lell.valid, g_learn, state.g)

        # -- postsynaptic dynamics (generated decay/apply code) ------------
        psm_ext = {"inj": inj, "dt": wu_ext["dt"], "t": wu_ext["t"]}
        if self.psm.needs_v:
            if v_post is None:
                raise ValueError(
                    f"synapse group {self.name!r}: postsynaptic model "
                    f"{self.psm.name!r} references V but the post population "
                    "has no membrane state 'V'")
            psm_ext["V"] = v_post
        new_psm, current = self._psm_step(state.psm, self.psm.params, psm_ext)

        new_state = SynapseState(psm=new_psm, wu_pre=new_pre,
                                 wu_post=new_post, g=new_g, syn=new_syn,
                                 dendritic=new_buf, cursor=new_cur)
        return new_state, current

    # -- memory accounting (paper eqs 1/2) ----------------------------------
    def state_elements(self) -> int:
        """Per-simulation dynamic state this group carries (one stream
        slot's worth): postsynaptic/trace/synapse vars, state-resident g,
        and the dendritic-delay ring + cursor.  Serving multiplies this by
        max_streams (each slot is an independent simulation)."""
        n_pre, n_post = self.ell.n_pre, self.ell.n_post
        nnz = n_pre * self.ell.max_conn
        total = (len(self.psm.state) * n_post
                 + len(self.wum.pre_state) * n_pre
                 + len(self.wum.post_state) * n_post
                 + len(self.wum.syn_state) * nnz)
        if self.plastic:
            total += nnz
        if self.needs_ring:
            total += self.ring_slots * n_post + 1
        return total

    def memory_report(self) -> dict:
        nnz = self.ell.n_pre * self.ell.max_conn
        return {
            "name": self.name,
            "representation": self.representation,
            "propagation": self.propagation_declared,
            "propagation_mode": self.propagation_mode,
            "event_capacity": self.event_capacity,
            "sparse_elements": F.sparse_memory_elements(
                nnz, self.ell.n_pre, self.ell.n_post),
            "dense_elements": F.dense_memory_elements(
                self.ell.n_pre, self.ell.n_post),
            "max_delay": self.max_delay,
            "dendritic_ring_elements": (
                self.ring_slots * self.ell.n_post if self.needs_ring else 0),
            "state_elements": self.state_elements(),
        }


def make_group(
    rng: np.random.Generator, name: str, pre: str, post: str,
    n_pre: int, n_post: int, n_conn: int, weight_fn=None,
    representation: str = "auto", connect: Optional[F.ConnectivityInit] = None,
    **kw,
) -> SynapseGroup:
    """Legacy front-end: build a group from a connectivity initializer
    (default: the paper's fixed-fanout construction).  Thin shim over the
    ModelSpec path — prefer repro.core.snn.spec for new code."""
    if connect is None:
        connect = F.FixedFanout(n_conn)
    post_ind, g, valid = connect.resolve(rng, n_pre, n_post, weight_fn)
    ell = F.triple_to_ell(post_ind, g, valid, n_post)
    return SynapseGroup(name=name, pre=pre, post=post, ell=ell,
                        representation=representation, **kw)
