"""The simulator: generates and runs the per-step update for a Network.

This is the JAX analogue of GeNN's generated simulation loop:

  for each step:
    1. synaptic propagation: last step's spikes -> post-synaptic currents
       (sparse ELL / dense matmul per the representation choice)
    2. neuron updates: the codegen'd model equations advance every population
    3. spike extraction (threshold / reset, or rising-edge detection)

`build_step` returns a pure function suitable for jax.jit / lax.scan / vmap;
`run` scans it.  gScale factors enter as *traced arguments* so a single
compiled simulator serves the whole conductance-scaling sweep (vmap over
candidates — the batch dimension the TPU spmv kernel wants).

External stimuli (`stim`): `step`/`run` accept per-population injected
currents — the serving path's per-request drive.  A stimulus is added to
Isyn *after* the population's input_fn, consuming no PRNG draws, so a run
with stim is bit-identical to the same run with that current folded into an
input_fn, and the serving engine's per-slot replay of a stimulus is
bit-identical to the offline run (the exactness contract
tests/test_serving.py pins down).

Synaptic delays are *dendritic* (GeNN's per-synapse delay model): each
group's weighted currents land in a post-side ring
`[max_delay+1, n_post]` (`SynapseState.dendritic`) `delay` slots ahead of
the cursor — see repro.core.snn.synapses.  The homogeneous `delay_steps=k`
shorthand lowers onto the same ring.

Streaming/serving (`init_stream_state` / `serve_chunk`): state gains a
leading *stream* axis (vmap) — `max_streams` independent simulations
resident on device, each slot carrying its own neuron/synapse/delay state
and PRNG key.  `serve_chunk` advances every slot up to `n_steps` with
per-slot `steps_left` masking: slot lanes past their remaining stimulus are
select-restored, so idle/finished slots are exact no-ops (state, key stream
and finite flag untouched).

NaN containment (paper §2): every step folds an `isfinite` reduction over
membrane state into a carried `finite` flag; overflow from an over-scaled
conductance is detected without host round-trips.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import codegen
from repro.core.snn import bitmask as BM
from repro.core.snn import custom_updates as CU
from repro.core.snn import probes as PR
from repro.core.snn.network import Network
from repro.core.snn.probes import Recordings
from repro.core.snn.synapses import SynapseState
from repro.obs import health as HE

__all__ = ["Simulator", "SimState", "RunResult"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SimState:
    neurons: Dict[str, Dict[str, jax.Array]]
    spikes: Dict[str, jax.Array]        # last step's spikes (bool)
    prev_above: Dict[str, jax.Array]    # for edge-spike populations
    syn: Dict[str, object]              # SynapseState per group name
    t: jax.Array                        # ms
    key: jax.Array
    finite: jax.Array                   # bool: no NaN/Inf so far

    def tree_flatten(self):
        return ((self.neurons, self.spikes, self.prev_above, self.syn,
                 self.t, self.key, self.finite), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RunResult:
    state: SimState
    spike_counts: Dict[str, jax.Array]   # per-neuron spike totals
    rates_hz: Dict[str, jax.Array]       # population mean rate
    finite: jax.Array
    raster: object = None                # legacy [steps, n] bool per pop
    recordings: object = None            # Recordings keyed by probe name
    health: object = None                # HealthReport when built monitored

    def tree_flatten(self):
        return ((self.state, self.spike_counts, self.rates_hz, self.finite,
                 self.raster, self.recordings, self.health), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.jit
def _select_streams(state, fresh, idx):
    """Gather/mix the stream axis: leaf-wise ``new[j] = old[idx[j]]`` when
    ``idx[j] >= 0`` else ``fresh[j]``.  jitted once and cached by shape —
    one executable per (old_size, new_size) bucket pair, shared by the host
    Simulator and the ShardedEngine (whose stream axis is unsharded, so a
    leading-axis gather never crosses devices)."""
    old_size = jax.tree.leaves(state)[0].shape[0]
    take = jnp.clip(idx, 0, old_size - 1)
    keep = idx >= 0

    def mix(old, fr):
        g = jnp.take(old, take, axis=0)
        m = keep.reshape((-1,) + (1,) * (g.ndim - 1))
        return jnp.where(m, g, fr.astype(g.dtype))

    return jax.tree.map(mix, state, fresh)


class Simulator:
    def __init__(self, net: Network, dt: float = 0.5, seed: int = 0,
                 probes=(), custom_updates=(), monitor=None):
        self.net = net
        self.dt = float(dt)
        self.seed = seed
        # --- opt-in health monitor (None / enabled=False -> identical
        # program: the scan body and carry never mention health) ---
        if monitor is not None and monitor.enabled:
            monitor.validate(net.populations)
            self.monitor = monitor
        else:
            self.monitor = None
        self._pop_sizes = {name: pop.n
                           for name, pop in net.populations.items()}
        # --- code generation: one update fn per population model ---
        self._updates = {
            name: codegen.compile_sim(pop.model)
            for name, pop in net.populations.items()
        }
        self._incoming = {
            name: [g for g in net.synapses if g.post == name]
            for name in net.populations
        }
        self._group_names = {g.name for g in net.synapses}
        self._groups = {g.name: g for g in net.synapses}
        self._run_jit_cache: Dict[tuple, object] = {}
        self._bind_lock = threading.RLock()
        # --- probes + custom updates (ModelSpec passes these resolved) ---
        self.probes = tuple(probes)
        self.custom_updates = {cu.name: cu for cu in custom_updates}
        self._scheduled = [cu for cu in custom_updates
                           if cu.every is not None]

    # ------------------------------------------------------------------
    # connectivity as a jit argument
    # ------------------------------------------------------------------
    def connectivity(self) -> Dict[str, tuple]:
        """Every synapse group's device arrays, {name: (ell, dense)}: the
        argument jitted programs take (with `bound`) instead of capturing
        the connectivity, which jit would embed in the compiled program as
        constants — gigabytes at 10^5 neurons x 10^3 synapses."""
        return {g.name: (g.ell, g.dense) for g in self.net.synapses}

    @contextlib.contextmanager
    def bound(self, conn: Dict[str, tuple]):
        """Trace with `conn` (the traced twin of `connectivity()`) standing
        in for each group's arrays.  Serialized: tracing swaps the groups'
        attributes, and the gateway traces from its pump thread."""
        with self._bind_lock:
            saved = self.connectivity()
            try:
                for g in self.net.synapses:
                    g.ell, g.dense = conn[g.name]
                yield
            finally:
                for g in self.net.synapses:
                    g.ell, g.dense = saved[g.name]

    def _validate_gscales(
            self, gscales: Optional[Mapping[str, jax.Array]]) -> None:
        """Reject gscale keys that match no synapse group (silent-typo
        hazard: a misspelled key used to be ignored via .get(name, 1.0))."""
        if not gscales:
            return
        unknown = set(gscales) - self._group_names
        if unknown:
            raise ValueError(
                f"unknown gscale key(s) {sorted(unknown)}; valid synapse "
                f"group names: {sorted(self._group_names)}")

    def _validate_stim(self, stim: Optional[Mapping[str, jax.Array]]) -> None:
        """Stim keys must name populations (same silent-typo hazard as
        gscales: a misspelled key would be an ignored no-op drive)."""
        if not stim:
            return
        unknown = set(stim) - set(self.net.populations)
        if unknown:
            raise ValueError(
                f"unknown stim population(s) {sorted(unknown)}; declared "
                f"populations: {sorted(self.net.populations)}")

    # ------------------------------------------------------------------
    def init_state(self, key: Optional[jax.Array] = None) -> SimState:
        if key is None:
            key = jax.random.PRNGKey(self.seed)
        neurons, spikes, prev_above = {}, {}, {}
        for name, pop in self.net.populations.items():
            neurons[name] = {
                k: jnp.full((pop.n,), v, jnp.float32)
                for k, v in pop.model.state.items()
            }
            spikes[name] = jnp.zeros((pop.n,), bool)
            if pop.edge_spikes:
                prev_above[name] = jnp.zeros((pop.n,), bool)
        syn = {g.name: g.init_state() for g in self.net.synapses}
        return SimState(neurons=neurons, spikes=spikes,
                        prev_above=prev_above, syn=syn,
                        t=jnp.zeros((), jnp.float32), key=key,
                        finite=jnp.ones((), bool))

    # ------------------------------------------------------------------
    def step(
        self, state: SimState,
        gscales: Optional[Mapping[str, jax.Array]] = None,
        stim: Optional[Mapping[str, jax.Array]] = None,
    ) -> Tuple[SimState, Dict[str, jax.Array]]:
        """One dt step. gscales: synapse-group name -> scalar multiplier;
        stim: population name -> [n] external current injected this step."""
        net, dt = self.net, self.dt
        self._validate_gscales(gscales)
        self._validate_stim(stim)
        gscales = gscales or {}
        stim = stim or {}
        with jax.named_scope("rng"):
            key, *subkeys = jax.random.split(state.key,
                                             1 + 2 * len(net.populations))
        subkeys = iter(subkeys)

        # 1. synaptic propagation (last step's spikes) ------------------
        isyn = {name: jnp.zeros((pop.n,), jnp.float32)
                for name, pop in net.populations.items()}
        new_syn = dict(state.syn)
        for g in net.synapses:
            with jax.named_scope(f"prop/{g.name}"):
                gs = jnp.asarray(gscales.get(g.name, 1.0), jnp.float32)
                v_post = state.neurons[g.post].get("V")
                s_new, cur = g.step(state.syn[g.name], state.spikes[g.pre],
                                    gs, dt, v_post=v_post,
                                    post_spikes=state.spikes[g.post],
                                    t=state.t)
                new_syn[g.name] = s_new
                isyn[g.post] = isyn[g.post] + cur

        # 2+3. neuron updates via generated code ------------------------
        new_neurons, new_spikes, new_prev = {}, {}, dict(state.prev_above)
        finite = state.finite
        for name, pop in net.populations.items():
            with jax.named_scope(f"neuron/{name}"):
                k_in, k_rand = next(subkeys), next(subkeys)
                cur = isyn[name]
                if pop.input_fn is not None:
                    cur = cur + pop.input_fn(k_in, state.t, pop.n)
                if name in stim:
                    cur = cur + jnp.asarray(stim[name], jnp.float32)
                ext = {"Isyn": cur, "dt": jnp.float32(dt), "t": state.t}
                if pop.model.needs_rand:
                    ext["rand"] = jax.random.uniform(k_rand, (pop.n,))
                ns, above = self._updates[name](state.neurons[name],
                                                pop.params, ext)
                if pop.edge_spikes:
                    spk = above & ~state.prev_above[name]
                    new_prev[name] = above
                else:
                    spk = above
                new_neurons[name] = ns
                new_spikes[name] = spk
                for arr in ns.values():
                    finite = finite & jnp.all(jnp.isfinite(arr))

        new_state = SimState(
            neurons=new_neurons, spikes=new_spikes, prev_above=new_prev,
            syn=new_syn, t=state.t + dt, key=key, finite=finite)
        new_state = self._run_scheduled_updates(new_state)
        return new_state, new_spikes

    # ------------------------------------------------------------------
    # custom updates (on-demand + scheduled)
    # ------------------------------------------------------------------
    def _run_scheduled_updates(self, state: SimState) -> SimState:
        """Apply every `every=n` custom update whose step count is due.
        The trigger is the global step counter round(t/dt), so scheduling
        is consistent across run/step/serving (a served stream fires at
        the same absolute steps as the offline oracle)."""
        if not self._scheduled:
            return state
        elapsed = jnp.int32(jnp.round(state.t / jnp.float32(self.dt)))
        for cu in self._scheduled:
            with jax.named_scope(f"custom/{cu.name}"):
                trig = (elapsed % cu.every) == 0
                state = self._apply_custom(state, cu, trig)
        return state

    def _apply_custom(self, state: SimState, cu, trig) -> SimState:
        """Apply one custom update, masked by the (scalar bool) trigger.
        Written arrays are folded into the carried NaN-guard flag (an
        update that divides by a zero reduction must trip `finite` just
        like an over-scaled conductance does)."""
        ext = {"dt": jnp.float32(self.dt), "t": state.t}
        if cu.kind == "group":
            grp = self._groups[cu.target]
            st = state.syn[cu.target]
            g_arr = st.g if st.g is not None else jnp.asarray(grp.ell.g)
            cu_vars = {"g": g_arr, **st.syn}
            red = {
                rname: CU.group_reduce_host(op, cu_vars[var], grp.ell,
                                            axis, cu.denom_all)
                for rname, (op, var, axis) in cu.reduce.items()}
            new = cu.fn(cu_vars, cu.params, red, ext)
            valid = grp.ell.valid

            def sel(name, old):
                if name not in cu.writes:
                    return old
                return jnp.where(trig, jnp.where(valid, new[name], old),
                                 old)

            ok = jnp.ones((), bool)
            for name in cu.writes:
                ok = ok & jnp.all(jnp.isfinite(
                    jnp.where(valid, new[name], 0.0)))
            finite = state.finite & jnp.where(trig, ok, True)
            new_syn = dict(state.syn)
            new_syn[cu.target] = SynapseState(
                psm=st.psm, wu_pre=st.wu_pre, wu_post=st.wu_post,
                g=(sel("g", g_arr) if st.g is not None else None),
                syn={k: sel(k, v) for k, v in st.syn.items()},
                dendritic=st.dendritic, cursor=st.cursor)
            return SimState(neurons=state.neurons, spikes=state.spikes,
                            prev_above=state.prev_above, syn=new_syn,
                            t=state.t, key=state.key, finite=finite)
        # population target
        cu_vars = dict(state.neurons[cu.target])
        red = {rname: CU.pop_reduce(op, cu_vars[var], cu.denom_all)
               for rname, (op, var, _axis) in cu.reduce.items()}
        new = cu.fn(cu_vars, cu.params, red, ext)
        ok = jnp.ones((), bool)
        for name in cu.writes:
            ok = ok & jnp.all(jnp.isfinite(new[name]))
        finite = state.finite & jnp.where(trig, ok, True)
        new_neurons = dict(state.neurons)
        new_neurons[cu.target] = {
            k: (jnp.where(trig, new[k], v) if k in cu.writes else v)
            for k, v in state.neurons[cu.target].items()}
        return SimState(neurons=new_neurons, spikes=state.spikes,
                        prev_above=state.prev_above, syn=state.syn,
                        t=state.t, key=state.key, finite=finite)

    def custom_update(self, state: SimState, name: str) -> SimState:
        """Run one declared custom update on demand (any `every`)."""
        if name not in self.custom_updates:
            raise ValueError(
                f"unknown custom update {name!r}; declared updates: "
                f"{sorted(self.custom_updates)}")
        return self._apply_custom(state, self.custom_updates[name],
                                  jnp.bool_(True))

    # ------------------------------------------------------------------
    # probe plumbing (shared by run and serve_chunk)
    # ------------------------------------------------------------------
    def _probe_init(self, n_steps: int, serving: bool = False):
        """Preallocated device-resident ring buffers, one per probe.
        Unreduced spike probes store uint32 bitmask rows (32x smaller);
        finalize unpacks them back to the documented bool layout."""
        bufs, caps = {}, {}
        for p in self.probes:
            cap = PR.capacity(p, n_steps, serving=serving)
            caps[p.name] = cap
            if PR.is_packed(p):
                bufs[p.name] = jnp.zeros((cap, BM.words_for(p.n)),
                                         jnp.uint32)
            else:
                bufs[p.name] = jnp.zeros((cap,) + p.sample_shape(), p.dtype)
        return bufs, caps

    def _probe_write(self, bufs, caps, start, i, state, spikes, gate=None):
        """One post-step sampling pass (strided ring write per probe)."""
        out = dict(bufs)
        for p in self.probes:
            with jax.named_scope("probe"):
                base = PR.probe_base(p, start)
                active, slot = PR.sample_slot(p, start, base, i,
                                              caps[p.name])
                if gate is not None:
                    active = active & gate
                val = PR.host_sample(p, self._groups, state, spikes)
                if PR.is_packed(p):
                    val = BM.pack_spikes(val)
                out[p.name] = PR.write_sample(bufs[p.name], slot, active,
                                              val)
        return out

    def _probe_finalize(self, bufs, caps, start, n_eff,
                        serving: bool = False) -> Recordings:
        data, counts = {}, {}
        for p in self.probes:
            with jax.named_scope("probe"):
                d, counts[p.name] = PR.finalize(
                    bufs[p.name], start, n_eff, p, caps[p.name],
                    use_window=not serving)
                data[p.name] = (BM.unpack_rows(d, p.n) if PR.is_packed(p)
                                else d)
        return Recordings(data=data, counts=counts)

    # ------------------------------------------------------------------
    # health monitor plumbing (repro.obs.health; engine mirrors these with
    # psum'd partial sums and lane-masked guards for bitwise parity)
    # ------------------------------------------------------------------
    def _health_counts(self, spikes) -> Dict[str, jax.Array]:
        """Per-population scalar int32 spike count for one step."""
        return {p: jnp.sum(spikes[p].astype(jnp.int32))
                for p in self._pop_sizes}

    def _health_ok(self, state: SimState) -> jax.Array:
        """Scalar bool: V (where the model has one) and plastic g all
        finite.  Invalid ELL slots are masked out — their g values are
        never read by the dynamics, so they must not trip the guard."""
        ok = jnp.ones((), bool)
        for name in self.net.populations:
            v = state.neurons[name].get("V")
            if v is not None:
                ok = ok & jnp.all(jnp.isfinite(v))
        for g in self.net.synapses:
            st = state.syn[g.name]
            if st.g is not None:
                ok = ok & jnp.all(jnp.isfinite(
                    jnp.where(g.ell.valid, st.g, 0.0)))
        return ok

    def _step_count(self, state: SimState) -> jax.Array:
        """Global step counter: probes and scheduled custom updates key
        their schedule off it so serving chunks line up with offline runs."""
        return jnp.int32(jnp.round(state.t / jnp.float32(self.dt)))

    # ------------------------------------------------------------------
    def run(
        self, state: SimState, n_steps: int,
        gscales: Optional[Mapping[str, jax.Array]] = None,
        record_raster: bool = False,
        stim: Optional[Mapping[str, jax.Array]] = None,
    ) -> RunResult:
        """Scan n_steps; returns spike statistics, probe recordings (and
        legacy rasters).  stim: population name -> [n_steps, n] external
        currents, one row injected per step (the serving path's offline
        oracle)."""
        self._validate_gscales(gscales)
        self._validate_stim(stim)
        stim = {k: jnp.asarray(v, jnp.float32) for k, v in (stim or {}).items()}
        start = self._step_count(state)
        bufs0, caps = self._probe_init(n_steps)
        mon = self.monitor

        def body(carry, xs):
            i, stim_t = xs
            if mon is not None:
                st, counts, bufs, hstate = carry
            else:
                st, counts, bufs = carry
            st2, spk = self.step(st, gscales, stim=stim_t)
            counts = {k: counts[k] + spk[k] for k in counts}
            bufs = self._probe_write(bufs, caps, start, i, st2, spk)
            out = spk if record_raster else None
            if mon is not None:
                with jax.named_scope("health"):
                    hstate = HE.accumulate(
                        mon, hstate, self._health_counts(spk),
                        self._health_ok(st2), self.dt, self._pop_sizes)
                return (st2, counts, bufs, hstate), out
            return (st2, counts, bufs), out

        counts0 = {name: jnp.zeros((pop.n,), jnp.int32)
                   for name, pop in self.net.populations.items()}
        xs = (jnp.arange(n_steps, dtype=jnp.int32),
              stim if stim else None)
        carry0 = (state, counts0, bufs0)
        if mon is not None:
            carry0 = carry0 + (HE.init_state(self._pop_sizes),)
        carry_out, raster = jax.lax.scan(body, carry0, xs, length=n_steps)
        if mon is not None:
            state2, counts, bufs, hstate = carry_out
            health = HE.finalize(mon, hstate, self.dt, self._pop_sizes)
        else:
            state2, counts, bufs = carry_out
            health = None
        rec = self._probe_finalize(bufs, caps, start, n_steps)

        t_sec = n_steps * self.dt * 1e-3
        rates = {k: jnp.mean(v) / t_sec for k, v in counts.items()}
        return RunResult(state=state2, spike_counts=counts, rates_hz=rates,
                         finite=state2.finite,
                         raster=raster if record_raster else None,
                         recordings=rec, health=health)

    # jit-compiled convenience wrapper (step count static) --------------
    def run_jit(self, n_steps: int, record_raster: bool = False):
        """Cached per (n_steps, record_raster), mirroring CompiledModel's
        executable cache: repeated calls with the same step count reuse one
        compiled program instead of re-jitting (gscale *values* are traced,
        so sweeping values also reuses it)."""
        cache_key = (int(n_steps), bool(record_raster))
        if cache_key not in self._run_jit_cache:

            @jax.jit
            def _run(conn, state, gscales):
                with self.bound(conn):
                    return self.run(state, n_steps, gscales,
                                    record_raster=record_raster)

            self._run_jit_cache[cache_key] = (
                lambda state, gscales: _run(self.connectivity(), state,
                                            gscales))
        return self._run_jit_cache[cache_key]

    # ------------------------------------------------------------------
    # streaming / serving: a leading stream axis over independent sims
    # ------------------------------------------------------------------
    def init_stream_state(self, keys: jax.Array) -> SimState:
        """Batched initial state: one independent simulation per slot.
        keys: [max_streams, ...] stacked PRNG keys (one per slot); every
        other leaf is the single-sim init broadcast along the stream axis,
        so slot s starts bit-identical to init_state(keys[s])."""
        return jax.vmap(self.init_state)(jnp.asarray(keys))

    def select_streams(self, state: SimState, idx, keys) -> SimState:
        """Re-pack the stream axis between chunks (slot reclamation and
        elastic resize).  New slot j continues old slot ``idx[j]``
        **bit-for-bit** when ``idx[j] >= 0``, else starts fresh from
        ``keys[j]``; ``len(idx)`` sets the new stream-axis size, so the
        same call grows, shrinks, compacts, or re-keys the slot table.
        Surviving slots are pure gathers — no arithmetic touches their
        state, which is what keeps mid-flight eviction/resize invisible to
        the streams that stay (tests/test_gateway.py pins this down)."""
        fresh = self.init_stream_state(jnp.asarray(keys))
        return _select_streams(state, fresh, jnp.asarray(idx, jnp.int32))

    def serve_chunk(
        self, state: SimState, stim: Mapping[str, jax.Array],
        steps_left: jax.Array, n_steps: int,
        gscales: Optional[Mapping[str, jax.Array]] = None,
        record_raster: bool = False,
    ):
        """Advance every stream slot by up to `n_steps` (one serving chunk).

        state: SimState with a leading stream axis (init_stream_state);
        stim: population -> [max_streams, n_steps, n] injected currents;
        steps_left: [max_streams] int32 — slot s advances
        min(steps_left[s], n_steps) steps; lanes at or past their budget are
        select-restored so idle/finished slots are exact no-ops.

        Returns (new_state, counts, raster, recordings): counts maps
        population -> [max_streams, n] spikes within the chunk (masked
        steps contribute zero); raster maps population ->
        [max_streams, n_steps, n] when record_raster (masked steps
        all-False), else None; recordings is a Recordings whose leaves
        carry a leading stream axis (per-slot sample counts in
        `.counts` — masked lanes take no samples).  Probe sampling keys
        off each slot's global step counter, so stitched chunks are
        bit-identical to the offline run's recordings.
        """
        self._validate_gscales(gscales)
        self._validate_stim(stim)
        stim = {k: jnp.asarray(v, jnp.float32) for k, v in stim.items()}
        steps_left = jnp.asarray(steps_left, jnp.int32)

        mon = self.monitor

        def one_stream(st, st_stim, left):
            start = self._step_count(st)
            bufs0, caps = self._probe_init(n_steps, serving=True)

            def body(carry, xs):
                t_idx, stim_t = xs
                if mon is not None:
                    st, counts, bufs, hstate = carry
                else:
                    st, counts, bufs = carry
                st2, spk = self.step(st, gscales, stim=stim_t)
                act = t_idx < left
                st2 = jax.tree.map(lambda a, b: jnp.where(act, a, b),
                                   st2, st)
                spk = {k: v & act for k, v in spk.items()}
                counts = {k: counts[k] + spk[k] for k in counts}
                bufs = self._probe_write(bufs, caps, start, t_idx, st2,
                                         spk, gate=act)
                out = spk if record_raster else None
                if mon is not None:
                    with jax.named_scope("health"):
                        hstate = HE.accumulate(
                            mon, hstate, self._health_counts(spk),
                            self._health_ok(st2), self.dt, self._pop_sizes,
                            gate=act)
                    return (st2, counts, bufs, hstate), out
                return (st2, counts, bufs), out

            counts0 = {name: jnp.zeros((pop.n,), jnp.int32)
                       for name, pop in self.net.populations.items()}
            xs = (jnp.arange(n_steps, dtype=jnp.int32),
                  st_stim if st_stim else None)
            carry0 = (st, counts0, bufs0)
            if mon is not None:
                carry0 = carry0 + (HE.init_state(self._pop_sizes),)
            carry_out, raster = jax.lax.scan(body, carry0, xs,
                                             length=n_steps)
            st2, counts, bufs = carry_out[:3]
            rec = self._probe_finalize(bufs, caps, start,
                                       jnp.minimum(left, n_steps),
                                       serving=True)
            if mon is not None:
                health = HE.finalize(mon, carry_out[3], self.dt,
                                     self._pop_sizes)
                return st2, counts, raster, rec, health
            return st2, counts, raster, rec

        return jax.vmap(one_stream)(state, stim, steps_left)
