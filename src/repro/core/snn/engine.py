"""ShardedEngine: multi-device SNN simulation over a jax.sharding mesh.

Populations are partitioned along the neuron axis: device d owns neuron
block d of *every* population and, for every synapse group, the slots whose
POST neuron lives in that block (`partition_ell_by_post`).  One step runs
entirely under `shard_map`:

  1. spike exchange: each device all-gathers the previous step's spikes
     (one small bool vector per pre population — the only per-step
     communication, following the distributed-construction literature);
  2. synaptic propagation: each device scatter-accumulates currents into
     its own post shard using its connectivity block (the compiled
     weight-update / postsynaptic snippets are reused unchanged via the
     `ell=`/`dense=` overrides of SynapseGroup.step); dendritic delays land
     those currents in the group's post-sharded delay ring — each device
     holds [max_delay+1, n_post_local], with per-synapse delay slots
     partitioned alongside the weights, so no delay state is replicated;
  3. neuron updates: the codegen'd model equations advance the local shard.

The engine is *bit-exact* against the single-device Simulator for the same
seed: the PRNG key schedule is replicated, `input_fn`/`rand` draws are
full-size and sliced per shard (the key must consume the same stream at any
device count), `stim` arrays are zero-padded and sharded along the mesh,
and the post-sharded connectivity preserves per-post-neuron scatter order.
STDP pre-trace vectors (`wu_pre`) shard along the PRE axis; the full trace
vector is all-gathered per step only when learn code reads it, so no
per-neuron or per-synapse plastic state is replicated.  Population sizes
are padded to a multiple of the device count; padded lanes carry
edge-replicated parameters, never spike, and are excluded from the finite
reduction and all outputs.

The whole n-step scan lives inside one shard_map call, so a run compiles to
a single program with one all-gather per (population, step).  `sweep_gscale`
vmaps the scan over candidates *inside* shard_map, composing the paper's
conductance sweep with neuron-axis parallelism.

Serving (`init_stream_state` / `serve_chunk`) reuses the same vmap-inside-
shard_map composition with a *stream* axis instead of the candidate axis:
`max_streams` independent simulations stay resident on device (each slot
its own neuron/synapse/delay state + PRNG key, every leaf gaining a leading
stream dim), and one compiled chunk program advances all slots together
under per-slot `steps_left` masking.  External stimuli enter zero-padded
and sharded along the neuron axis, so a served stream is bit-exact against
the offline `run(..., stim=...)`.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import codegen
from repro.core.snn import bitmask as BM
from repro.core.snn import custom_updates as CU
from repro.core.snn import probes as PR
from repro.core.snn.network import Network
from repro.core.snn.probes import Recordings
from repro.core.snn.simulator import (RunResult, SimState,
                                      _select_streams)
from repro.core.snn.synapses import LocalConnectivity, SynapseState
from repro.launch.mesh import snn_axis
from repro.launch.sharding import neuron_pad, pad_neuron_axis, snn_shardings
from repro.obs import health as HE
from repro.obs import trace
from repro.sparse import formats as F
from repro.sparse.device_init import device_init_local, partition_ell_by_post

__all__ = ["ShardedEngine"]


class ShardedEngine:
    """Runs a built Network partitioned over a 1-D device mesh."""

    def __init__(self, net: Network, mesh, dt: float = 0.5, seed: int = 0,
                 probes=(), custom_updates=(), monitor=None,
                 local_init=None):
        """local_init: optional {group name -> LocalInitPlan} — groups with
        a plan build their post-sharded connectivity blocks with
        `device_init_local` (each device generates only the rows it owns,
        O(nnz/device) peak construction memory) instead of materializing
        the full ELL and calling `partition_ell_by_post`.  Bit-exact
        either way; `ModelSpec.build(init="device", mesh=...)` wires this
        automatically."""
        self.net = net
        self.mesh = mesh
        self.axis = snn_axis(mesh)
        self.n_shards = int(mesh.shape[self.axis])
        self.dt = float(dt)
        self.seed = seed
        # --- opt-in health monitor (same gating as the host Simulator:
        # None / enabled=False never touches the compiled program) ---
        if monitor is not None and monitor.enabled:
            monitor.validate(net.populations)
            self.monitor = monitor
        else:
            self.monitor = None
        self._pop_sizes = {name: pop.n
                           for name, pop in net.populations.items()}
        self._updates = {
            name: codegen.compile_sim(pop.model)
            for name, pop in net.populations.items()
        }
        self._group_names = {g.name for g in net.synapses}
        self._groups = {g.name: g for g in net.synapses}
        self.probes = tuple(probes)
        self.custom_updates = {cu.name: cu for cu in custom_updates}
        self._scheduled = [cu for cu in custom_updates
                           if cu.every is not None]
        D = self.n_shards
        self._npad = {name: neuron_pad(pop.n, D)
                      for name, pop in net.populations.items()}
        self._shard = {name: self._npad[name] // D for name in self._npad}

        self._sh = snn_shardings(mesh, self.axis)
        sh_block = self._sh["block"]
        sh_neuron = self._sh["neuron"]

        # --- partition connectivity: post-shard every group ---------------
        # blocks[gname]: {"g","post","valid"} each [D, n_pre, K_local], or
        # {"dense"}: [D, n_pre, shard] column blocks of the dense mirror.
        self._blocks: Dict[str, Dict[str, jax.Array]] = {}
        self._block_specs: Dict[str, Dict[str, P]] = {}
        self._k_local: Dict[str, int] = {}
        for g in net.synapses:
            n_post_pad = self._npad[g.post]
            if g.representation == "dense" and not g.plastic:
                w = jnp.pad(g.dense,
                            ((0, 0), (0, n_post_pad - g.ell.n_post)))
                blk = w.reshape(g.ell.n_pre, D, n_post_pad // D)
                blk = jnp.moveaxis(blk, 1, 0)
                self._blocks[g.name] = {
                    "dense": jax.device_put(blk, sh_block)}
                self._block_specs[g.name] = {"dense": P(self.axis, None,
                                                        None)}
            else:
                plan = (local_init or {}).get(g.name)
                if plan is not None:
                    # fused local construction: each device generates only
                    # its own rows inside shard_map and exchanges finished
                    # post-sharded slots — the full ELL is never
                    # materialized on any single device
                    with trace.span("device_init_local", group=g.name,
                                    rows=g.ell.n_pre, devices=D):
                        (gg, post, valid, delay, shard_size,
                         k_loc) = device_init_local(
                             plan.connect, plan.key, plan.n_pre,
                             plan.n_post_total, self.mesh,
                             weight=plan.weight, delay=plan.delay,
                             axis=self.axis,
                             post_window=plan.post_window)
                else:
                    with trace.span("partition_ell_by_post", group=g.name,
                                    rows=g.ell.n_pre, k=g.ell.max_conn,
                                    devices=D):
                        (gg, post, valid, delay, shard_size,
                         k_loc) = partition_ell_by_post(g.ell, D)
                assert shard_size == self._shard[g.post]
                self._k_local[g.name] = k_loc
                self._blocks[g.name] = {
                    "g": jax.device_put(gg, sh_block),
                    "post": jax.device_put(post, sh_block),
                    "valid": jax.device_put(valid, sh_block),
                }
                if delay is not None:
                    # per-synapse dendritic delays ride in the same
                    # post-sharded layout as the weights they gate
                    self._blocks[g.name]["delay"] = jax.device_put(
                        delay, sh_block)
                self._block_specs[g.name] = {
                    k: P(self.axis, None, None)
                    for k in self._blocks[g.name]}

        # --- per-neuron parameter arrays (scalars stay baked) -------------
        self._pn_params: Dict[str, Dict[str, jax.Array]] = {}
        self._pn_specs: Dict[str, Dict[str, P]] = {}
        self._scalar_params: Dict[str, Dict[str, object]] = {}
        for name, pop in net.populations.items():
            pn, sc = {}, {}
            for k, v in pop.params.items():
                arr = jnp.asarray(v)
                if arr.ndim and arr.shape[0] == pop.n:
                    pn[k] = jax.device_put(
                        pad_neuron_axis(arr, self._npad[name]), sh_neuron)
                else:
                    sc[k] = v
            self._pn_params[name] = pn
            self._pn_specs[name] = {k: P(self.axis) for k in pn}
            self._scalar_params[name] = sc

        self._state_specs = self._make_state_specs()
        self._run_cache: Dict[tuple, Callable] = {}
        self._sweep_cache: Dict[tuple, Callable] = {}
        self._step_cache: Dict[tuple, Callable] = {}
        self._serve_cache: Dict[tuple, Callable] = {}
        self._custom_cache: Dict[str, Callable] = {}

    @property
    def blocks(self) -> Dict[str, Dict[str, jax.Array]]:
        """Per-group post-sharded connectivity: {"g", "post", "valid"
        [, "delay"]} each [D, n_pre, K_local], or {"dense"}
        [D, n_pre, shard]; shard d lives on mesh device d."""
        return self._blocks

    # ------------------------------------------------------------------
    # state layout
    # ------------------------------------------------------------------
    def _make_state_specs(self) -> SimState:
        net, ax = self.net, self.axis
        neurons = {name: {k: P(ax) for k in pop.model.state}
                   for name, pop in net.populations.items()}
        spikes = {name: P(ax) for name in net.populations}
        prev = {name: P(ax) for name, pop in net.populations.items()
                if pop.edge_spikes}
        syn = {}
        for g in net.synapses:
            # spec twin of each SynapseState: same pytree nodes, P leaves.
            # The dendritic ring is post-sized, so it shards on the neuron
            # axis like every other post-side buffer, and the wu_pre STDP
            # traces shard along the PRE axis — no per-neuron or
            # per-synapse plastic state is replicated across devices.
            syn[g.name] = SynapseState(
                psm={k: P(ax) for k in g.psm.state},
                wu_pre={k: P(ax) for k in g.wum.pre_state},
                wu_post={k: P(ax) for k in g.wum.post_state},
                g=P(ax, None, None) if g.plastic else None,
                syn={k: P(ax, None, None) for k in g.wum.syn_state},
                dendritic=P(None, ax) if g.needs_ring else None,
                cursor=P() if g.needs_ring else None)
        return SimState(neurons=neurons, spikes=spikes, prev_above=prev,
                        syn=syn, t=P(), key=P(), finite=P())

    def init_state(self, key: Optional[jax.Array] = None) -> SimState:
        """Initial sharded state, bit-equivalent to Simulator.init_state on
        the real lanes (padding lanes replicate the init constants)."""
        if key is None:
            key = jax.random.PRNGKey(self.seed)
        net, D = self.net, self.n_shards
        shn = self._sh["neuron"]
        shr = self._sh["replicated"]
        shb = self._sh["block"]
        put = jax.device_put
        neurons, spikes, prev = {}, {}, {}
        for name, pop in net.populations.items():
            npad = self._npad[name]
            neurons[name] = {
                k: put(jnp.full((npad,), v, jnp.float32), shn)
                for k, v in pop.model.state.items()}
            spikes[name] = put(jnp.zeros((npad,), bool), shn)
            if pop.edge_spikes:
                prev[name] = put(jnp.zeros((npad,), bool), shn)
        syn = {}
        for g in net.synapses:
            n_pre = g.ell.n_pre
            npost_pad = self._npad[g.post]
            psm = {k: put(jnp.full((npost_pad,), v, jnp.float32), shn)
                   for k, v in g.psm.state.items()}
            # pre traces shard along the pre-population neuron axis
            # (padded lanes carry the init constant and never spike)
            wu_pre = {k: put(jnp.full((self._npad[g.pre],), v,
                                      jnp.float32), shn)
                      for k, v in g.wum.pre_state.items()}
            wu_post = {k: put(jnp.full((npost_pad,), v, jnp.float32), shn)
                       for k, v in g.wum.post_state.items()}
            gv = (put(self._blocks[g.name]["g"], shb) if g.plastic
                  else None)
            syn_vars = {
                k: put(jnp.full((D, n_pre, self._k_local[g.name]), v,
                                jnp.float32), shb)
                for k, v in g.wum.syn_state.items()}
            if g.needs_ring:
                # dendritic ring sharded along the post axis: each device
                # holds [ring_slots, n_post_local], never a replicated
                # pre-sized buffer
                buf = put(jnp.zeros((g.ring_slots, npost_pad),
                                    jnp.float32), self._sh["ring"])
                cur = put(jnp.zeros((), jnp.int32), shr)
            else:
                buf, cur = None, None
            syn[g.name] = SynapseState(psm=psm, wu_pre=wu_pre,
                                       wu_post=wu_post, g=gv, syn=syn_vars,
                                       dendritic=buf, cursor=cur)
        return SimState(
            neurons=neurons, spikes=spikes, prev_above=prev, syn=syn,
            t=put(jnp.zeros((), jnp.float32), shr), key=put(key, shr),
            finite=put(jnp.ones((), bool), shr))

    # ------------------------------------------------------------------
    # local (per-device) computation
    # ------------------------------------------------------------------
    def _squeeze_blocks(self, tree):
        """[1, n_pre, K] local views -> [n_pre, K]."""
        return jax.tree.map(lambda x: x[0] if x.ndim == 3 else x, tree)

    def _squeeze_syn(self, syn):
        out = {}
        for name, s in syn.items():
            out[name] = s.__class__(
                psm=s.psm, wu_pre=s.wu_pre, wu_post=s.wu_post,
                g=None if s.g is None else s.g[0],
                syn={k: v[0] for k, v in s.syn.items()},
                dendritic=s.dendritic, cursor=s.cursor)
        return out

    def _unsqueeze_syn(self, syn):
        out = {}
        for name, s in syn.items():
            out[name] = s.__class__(
                psm=s.psm, wu_pre=s.wu_pre, wu_post=s.wu_post,
                g=None if s.g is None else s.g[None],
                syn={k: v[None] for k, v in s.syn.items()},
                dendritic=s.dendritic, cursor=s.cursor)
        return out

    def _local_step(self, state: SimState, blocks, pn_params,
                    gscales: Mapping[str, jax.Array],
                    stim: Optional[Mapping[str, jax.Array]] = None):
        """One dt step on this device's shard; mirrors Simulator.step
        line for line (key schedule, group order, update order).
        stim: population -> [S] local shard of zero-padded external
        currents (sharded along the neuron axis by _pad_stim)."""
        stim = stim or {}
        net, dt, ax = self.net, self.dt, self.axis
        d = jax.lax.axis_index(ax)
        with jax.named_scope("rng"):
            key, *subkeys = jax.random.split(state.key,
                                             1 + 2 * len(net.populations))
        subkeys = iter(subkeys)

        # 0. spike exchange, bit-packed (GeNN's 32x spike bitmask): each
        # device packs its bool shard into uint32 words, all-gathers the
        # words — 8x less wire traffic than gathering bool bytes — and
        # unpacks device-locally.  Round-trip is exact, so the gathered
        # vector is bitwise the old one.
        full_spikes = {}
        D = self.n_shards
        for name in sorted({g.pre for g in net.synapses}):
            with jax.named_scope("exchange"):
                seg = self._shard[name]
                words = BM.pack_spikes(state.spikes[name])
                fw = jax.lax.all_gather(words, ax, tiled=True)
                full = BM.unpack_segments(
                    fw.reshape(D, BM.words_for(seg)), seg)
                full_spikes[name] = full[: net.populations[name].n]

        # 1. synaptic propagation into the local post shard --------------
        isyn = {name: jnp.zeros((self._shard[name],), jnp.float32)
                for name in net.populations}
        new_syn = dict(state.syn)
        for g in net.synapses:
            with jax.named_scope(f"prop/{g.name}"):
                gs = jnp.asarray(gscales.get(g.name, 1.0), jnp.float32)
                blk = blocks[g.name]
                if "dense" in blk:
                    ell_l, dense_l = None, blk["dense"]
                    # a local ELL stand-in keeps post-side shapes consistent
                    ell_l = F.ELLSynapses(
                        g=jnp.zeros((g.ell.n_pre, 1), jnp.float32),
                        post_ind=jnp.zeros((g.ell.n_pre, 1), jnp.int32),
                        valid=jnp.zeros((g.ell.n_pre, 1), bool),
                        n_post=self._shard[g.post])
                else:
                    ell_l = F.ELLSynapses(g=blk["g"], post_ind=blk["post"],
                                          valid=blk["valid"],
                                          n_post=self._shard[g.post],
                                          delay=blk.get("delay"))
                    dense_l = None
                v_post = state.neurons[g.post].get("V")
                new_pre_local = None
                pre_arg = None
                if g.wum.pre_state:
                    # wu_pre shards along the PRE axis: advance the local
                    # trace segment (the elementwise pre_step commutes with
                    # slicing; padded lanes never spike) and gather the full
                    # vector only when learn code actually reads it — a
                    # per-step transient, never replicated persistent state
                    new_pre_local = state.syn[g.name].wu_pre
                    if g._wu.pre_step is not None:
                        new_pre_local = g._wu.pre_step(
                            state.syn[g.name].wu_pre, g.wum.params,
                            {"dt": dt, "t": state.t,
                             "delay": jnp.float32(g.delay_steps),
                             "pre_spike":
                                 state.spikes[g.pre].astype(jnp.float32)})
                    pre_arg = {}
                    if g._wu.learn is not None:
                        with jax.named_scope("exchange"):
                            pre_arg = {
                                k: jax.lax.all_gather(
                                    v, ax, tiled=True)[: g.ell.n_pre]
                                for k, v in new_pre_local.items()}
                s_new, cur = g.step(
                    state.syn[g.name], full_spikes[g.pre], gs, dt,
                    v_post=v_post, post_spikes=state.spikes[g.post], t=state.t,
                    conn=LocalConnectivity(ell=ell_l, dense=dense_l),
                    pre_traces=pre_arg)
                if new_pre_local is not None:
                    s_new = s_new.__class__(
                        psm=s_new.psm, wu_pre=new_pre_local,
                        wu_post=s_new.wu_post, g=s_new.g, syn=s_new.syn,
                        dendritic=s_new.dendritic, cursor=s_new.cursor)
                new_syn[g.name] = s_new
                isyn[g.post] = isyn[g.post] + cur

        # 2+3. neuron updates on the local shard -------------------------
        new_neurons, new_spikes = {}, {}
        new_prev = dict(state.prev_above)
        finite = state.finite
        for name, pop in net.populations.items():
            with jax.named_scope(f"neuron/{name}"):
                k_in, k_rand = next(subkeys), next(subkeys)
                S = self._shard[name]
                lane = d * S + jnp.arange(S)
                lane_valid = lane < pop.n
                cur = isyn[name]
                if pop.input_fn is not None:
                    # full-size draw + slice: bit-identical to the unsharded
                    # path (the key consumes the same stream regardless of D)
                    full = pop.input_fn(k_in, state.t, pop.n)
                    full = jnp.pad(full, (0, self._npad[name] - pop.n))
                    cur = cur + jax.lax.dynamic_slice(full, (d * S,), (S,))
                if name in stim:
                    # stim arrives zero-padded and sharded along the neuron
                    # axis (see _pad_stim): the local segment adds directly —
                    # bit-identical to the old replicated draw + slice, with
                    # 1/D the per-device footprint
                    cur = cur + jnp.asarray(stim[name], jnp.float32)
                params = dict(self._scalar_params[name])
                params.update(pn_params[name])
                ext = {"Isyn": cur, "dt": jnp.float32(dt), "t": state.t}
                if pop.model.needs_rand:
                    full = jax.random.uniform(k_rand, (pop.n,))
                    full = jnp.pad(full, (0, self._npad[name] - pop.n))
                    ext["rand"] = jax.lax.dynamic_slice(full, (d * S,), (S,))
                ns, above = self._updates[name](state.neurons[name], params,
                                               ext)
                if pop.edge_spikes:
                    spk = above & ~state.prev_above[name]
                    new_prev[name] = above
                else:
                    spk = above
                new_neurons[name] = ns
                new_spikes[name] = spk & lane_valid
                for arr in ns.values():
                    finite = finite & jnp.all(
                        jnp.isfinite(jnp.where(lane_valid, arr, 0.0)))

        new_state = SimState(
            neurons=new_neurons, spikes=new_spikes, prev_above=new_prev,
            syn=new_syn, t=state.t + dt, key=key, finite=finite)
        new_state = self._run_scheduled_local(new_state, blocks, pn_params)
        return new_state, new_spikes

    def _combine_finite(self, finite):
        return jax.lax.pmin(finite.astype(jnp.int32), self.axis) == 1

    # ------------------------------------------------------------------
    # health monitor plumbing (mirrors Simulator._health_* with psum'd
    # partial sums and lane/slot-masked guards; integer psum keeps the
    # per-step counts — and hence every downstream float op — bitwise
    # identical to the host path)
    # ------------------------------------------------------------------
    def _health_counts_local(self, spikes) -> Dict[str, jax.Array]:
        """Full-population scalar int32 spike count for one step (local
        spikes are already lane_valid-masked, so padded lanes add 0)."""
        return {p: jax.lax.psum(jnp.sum(spikes[p].astype(jnp.int32)),
                                self.axis)
                for p in self._pop_sizes}

    def _health_ok_local(self, state: SimState, blocks) -> jax.Array:
        """This device's shard of the NaN guard: V on valid lanes, plastic
        g on valid ELL slots.  Per-device verdicts are merged at scan exit
        (HE.combine_across_devices), preserving the host's first-bad-step."""
        ok = jnp.ones((), bool)
        d = jax.lax.axis_index(self.axis)
        for name, pop in self.net.populations.items():
            v = state.neurons[name].get("V")
            if v is not None:
                S = self._shard[name]
                lane_valid = d * S + jnp.arange(S) < pop.n
                ok = ok & jnp.all(jnp.isfinite(
                    jnp.where(lane_valid, v, 0.0)))
        for g in self.net.synapses:
            st = state.syn[g.name]
            if st.g is not None:
                ok = ok & jnp.all(jnp.isfinite(
                    jnp.where(blocks[g.name]["valid"], st.g, 0.0)))
        return ok

    # ------------------------------------------------------------------
    # custom updates on the local shard (mirrors Simulator._apply_custom;
    # cross-device reductions via psum/pmax/pmin, per-post reductions are
    # device-local because each device owns its post shard)
    # ------------------------------------------------------------------
    def _run_scheduled_local(self, state: SimState, blocks,
                             pn_params) -> SimState:
        if not self._scheduled:
            return state
        elapsed = jnp.int32(jnp.round(state.t / jnp.float32(self.dt)))
        for cu in self._scheduled:
            with jax.named_scope(f"custom/{cu.name}"):
                trig = (elapsed % cu.every) == 0
                state = self._apply_custom_local(state, cu, trig, blocks,
                                                 pn_params)
        return state

    def _group_reduce_local(self, op, val, blk, axis, denom_all: float,
                            n_post_local: int):
        """One declared group reduction on this device's connectivity
        block.  'post' needs no communication (the device owns every
        synapse targeting its post shard); 'pre'/'all' combine per-device
        partials with psum/pmax/pmin."""
        ax = self.axis
        valid = blk["valid"]
        neutral = PR.reduce_neutral(op)
        masked = jnp.where(valid, jnp.asarray(val, jnp.float32), neutral)
        if axis == "post":
            per_post = CU._scatter_post(val, blk["post"], valid,
                                        n_post_local, op)
            return CU.gather_post(per_post, blk["post"])
        if axis == "pre":
            if op in ("sum", "mean"):
                rs = jax.lax.psum(jnp.sum(
                    jnp.where(valid, jnp.asarray(val, jnp.float32), 0.0),
                    axis=1), ax)
                if op == "sum":
                    return rs[:, None]
                cnt = jax.lax.psum(
                    jnp.sum(valid.astype(jnp.float32), axis=1), ax)
                return jnp.where(cnt > 0, rs / jnp.maximum(cnt, 1.0),
                                 0.0)[:, None]
            part = (jnp.max(masked, axis=1) if op == "max"
                    else jnp.min(masked, axis=1))
            comb = jax.lax.pmax if op == "max" else jax.lax.pmin
            return comb(part, ax)[:, None]
        # axis == "all": scalar over the whole matrix
        if op in ("sum", "mean"):
            tot = jax.lax.psum(jnp.sum(
                jnp.where(valid, jnp.asarray(val, jnp.float32), 0.0)), ax)
            return tot / jnp.float32(denom_all) if op == "mean" else tot
        part = jnp.max(masked) if op == "max" else jnp.min(masked)
        comb = jax.lax.pmax if op == "max" else jax.lax.pmin
        return comb(part, ax)

    def _pop_reduce_local(self, op, val, lane_valid, denom: float):
        """Population-axis reduction over the local shard, combined
        across devices (padded lanes neutral-masked)."""
        ax = self.axis
        neutral = PR.reduce_neutral(op)
        masked = jnp.where(lane_valid, jnp.asarray(val, jnp.float32),
                           neutral)
        if op in ("sum", "mean"):
            tot = jax.lax.psum(jnp.sum(
                jnp.where(lane_valid, jnp.asarray(val, jnp.float32),
                          0.0)), ax)
            return tot / jnp.float32(denom) if op == "mean" else tot
        part = jnp.max(masked) if op == "max" else jnp.min(masked)
        comb = jax.lax.pmax if op == "max" else jax.lax.pmin
        return comb(part, ax)

    def _apply_custom_local(self, state: SimState, cu, trig, blocks,
                            pn_params) -> SimState:
        ext = {"dt": jnp.float32(self.dt), "t": state.t}
        if cu.kind == "group":
            grp = self._groups[cu.target]
            blk = blocks[cu.target]
            st = state.syn[cu.target]
            g_arr = st.g if st.g is not None else blk["g"]
            cu_vars = {"g": g_arr, **st.syn}
            red = {
                rname: self._group_reduce_local(
                    op, cu_vars[var], blk, axis, cu.denom_all,
                    self._shard[grp.post])
                for rname, (op, var, axis) in cu.reduce.items()}
            new = cu.fn(cu_vars, cu.params, red, ext)
            valid = blk["valid"]

            def sel(name, old):
                if name not in cu.writes:
                    return old
                return jnp.where(trig, jnp.where(valid, new[name], old),
                                 old)

            # NaN guard: the update's writes must trip `finite` exactly
            # like an over-scaled conductance would (local check; the
            # run/step wrappers pmin-combine across devices)
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
        pop = self.net.populations[cu.target]
        d = jax.lax.axis_index(self.axis)
        S = self._shard[cu.target]
        lane_valid = d * S + jnp.arange(S) < pop.n
        cu_vars = dict(state.neurons[cu.target])
        red = {rname: self._pop_reduce_local(op, cu_vars[var], lane_valid,
                                             cu.denom_all)
               for rname, (op, var, _axis) in cu.reduce.items()}
        # cu.params carries the resolve-time merge (update params + full
        # pop params); re-overlay the population params with their local
        # shard / baked-scalar forms
        params = dict(cu.params)
        params.update(self._scalar_params[cu.target])
        params.update(pn_params[cu.target])
        new = cu.fn(cu_vars, params, red, ext)
        ok = jnp.ones((), bool)
        for name in cu.writes:
            ok = ok & jnp.all(jnp.isfinite(
                jnp.where(lane_valid, new[name], 0.0)))
        finite = state.finite & jnp.where(trig, ok, True)
        new_neurons = dict(state.neurons)
        new_neurons[cu.target] = {
            k: (jnp.where(trig, new[k], v) if k in cu.writes else v)
            for k, v in state.neurons[cu.target].items()}
        return SimState(neurons=new_neurons, spikes=state.spikes,
                        prev_above=state.prev_above, syn=state.syn,
                        t=state.t, key=state.key, finite=finite)

    # ------------------------------------------------------------------
    # probes on the local shard.  Per-neuron-shaped probes store local
    # rows (the buffer shards along the neuron axis, gathered on exit);
    # reduced per-neuron probes all-gather the full vector and apply the
    # identical reduction (bit-exact vs the host build); synapse-matrix
    # reductions combine per-device partials with psum/pmax/pmin.
    # ------------------------------------------------------------------
    def _probe_sharded(self, p) -> bool:
        """True when the probe's buffer rows shard along the neuron axis
        (wu_pre buffers shard along the PRE population's axis)."""
        return p.reduce is None

    def _probe_local_shape(self, p, cap: int):
        if p.reduce is not None:
            return (cap,)
        if p.varkind == "wu_pre":
            return (cap, self._shard[self._groups[p.target].pre])
        if PR.is_packed(p):
            # spike rows live as uint32 bitmasks (32x smaller rings);
            # unpacked shard-locally at finalize, before the exit gather
            return (cap, BM.words_for(self._shard[p.target]))
        if p.kind == "population":
            return (cap, self._shard[p.target])
        return (cap, self._shard[self._groups[p.target].post])

    def _probe_init_local(self, n_steps: int, serving: bool = False):
        bufs, caps = {}, {}
        for p in self.probes:
            cap = PR.capacity(p, n_steps, serving=serving)
            caps[p.name] = cap
            bufs[p.name] = jnp.zeros(self._probe_local_shape(p, cap),
                                     jnp.uint32 if PR.is_packed(p)
                                     else p.dtype)
        return bufs, caps

    def _probe_local_value(self, p, state, spikes, blocks):
        ax = self.axis
        if p.varkind == "wu_pre":
            val = state.syn[p.target].wu_pre[p.var]   # local pre shard
            if p.reduce is None:
                return val                            # sharded buffer rows
            full = jax.lax.all_gather(val, ax, tiled=True)[: p.n]
            return PR.vector_reduce(full, p.reduce, p.denom)
        if p.varkind in ("g", "syn"):
            blk = blocks[p.target]
            st = state.syn[p.target]
            val = st.g if p.varkind == "g" else st.syn[p.var]
            op = p.reduce
            masked = jnp.where(blk["valid"], jnp.asarray(val, jnp.float32),
                               PR.reduce_neutral(op))
            if op in ("sum", "mean"):
                tot = jax.lax.psum(jnp.sum(
                    jnp.where(blk["valid"],
                              jnp.asarray(val, jnp.float32), 0.0)), ax)
                return tot / jnp.float32(p.denom) if op == "mean" else tot
            part = jnp.max(masked) if op == "max" else jnp.min(masked)
            comb = jax.lax.pmax if op == "max" else jax.lax.pmin
            return comb(part, ax)
        if p.varkind == "neuron":
            val = state.neurons[p.target][p.var]
        elif p.varkind == "spikes":
            val = spikes[p.target]
        elif p.varkind == "psm":
            val = state.syn[p.target].psm[p.var]
        else:  # wu_post
            val = state.syn[p.target].wu_post[p.var]
        if p.reduce is None:
            return val                              # local shard rows
        full = jax.lax.all_gather(val, ax, tiled=True)[: p.n]
        return PR.vector_reduce(full, p.reduce, p.denom)

    def _probe_write_local(self, bufs, caps, start, i, state, spikes,
                           blocks, gate=None):
        out = dict(bufs)
        for p in self.probes:
            with jax.named_scope("probe"):
                base = PR.probe_base(p, start)
                active, slot = PR.sample_slot(p, start, base, i,
                                              caps[p.name])
                if gate is not None:
                    active = active & gate
                val = self._probe_local_value(p, state, spikes, blocks)
                if PR.is_packed(p):
                    val = BM.pack_spikes(val)
                out[p.name] = PR.write_sample(bufs[p.name], slot, active,
                                              val)
        return out

    def _probe_finalize_local(self, bufs, caps, start, n_eff,
                              serving: bool = False):
        data, counts = {}, {}
        for p in self.probes:
            with jax.named_scope("probe"):
                d, counts[p.name] = PR.finalize(
                    bufs[p.name], start, n_eff, p, caps[p.name],
                    use_window=not serving)
                if PR.is_packed(p):
                    # unpack to the local shard width while still inside
                    # shard_map, so the exit gather/crop contract is
                    # unchanged
                    d = BM.unpack_rows(d, self._shard[p.target])
                data[p.name] = d
        return data, counts

    def _probe_out_specs(self, lead=()):
        """(data specs, count specs) keyed by probe name; `lead` prefixes
        extra unsharded axes (sweep candidates / serving streams)."""
        data, counts = {}, {}
        for p in self.probes:
            if self._probe_sharded(p):
                data[p.name] = P(*lead, None, self.axis)
            elif p.reduce is None:
                data[p.name] = P(*lead, None, None)
            else:
                data[p.name] = P(*lead, None)
            counts[p.name] = P(*lead)
        return data, counts

    def _crop_probe_data(self, data):
        """Gathered neuron-sharded buffers carry padded lanes; crop them."""
        return {p.name: (data[p.name][..., : p.n]
                         if self._probe_sharded(p) else data[p.name])
                for p in self.probes}

    def _step_count(self, state: SimState) -> jax.Array:
        return jnp.int32(jnp.round(state.t / jnp.float32(self.dt)))

    # ------------------------------------------------------------------
    # compiled entry points (cached like CompiledModel)
    # ------------------------------------------------------------------
    def _validate_gscales(self, gscales) -> None:
        if not gscales:
            return
        unknown = set(gscales) - self._group_names
        if unknown:
            raise ValueError(
                f"unknown gscale key(s) {sorted(unknown)}; valid synapse "
                f"group names: {sorted(self._group_names)}")

    def _validate_stim(self, stim) -> None:
        if not stim:
            return
        unknown = set(stim) - set(self.net.populations)
        if unknown:
            raise ValueError(
                f"unknown stim population(s) {sorted(unknown)}; declared "
                f"populations: {sorted(self.net.populations)}")

    def _pad_stim(self, stim) -> Dict[str, jax.Array]:
        """Zero-pad each stim array's neuron axis (the last) to the padded
        population size so it enters shard_map sharded along the mesh
        instead of replicated.  Padded lanes add 0 into padded shard lanes
        (masked out of every output), so this is bit-identical to the old
        full-size replicated array + per-device dynamic_slice at 1/D the
        per-device footprint."""
        out = {}
        for k, v in stim.items():
            arr = jnp.asarray(v, jnp.float32)
            pad = self._npad[k] - self.net.populations[k].n
            if pad:
                cfg = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
                arr = jnp.pad(arr, cfg)
            out[k] = arr
        return out

    def _in_specs(self):
        return (self._state_specs, self._block_specs, self._pn_specs)

    def _shard_map(self, fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def _make_run(self, n_steps: int, keys: Tuple[str, ...],
                  record_raster: bool, stim_keys: Tuple[str, ...] = ()):
        mon = self.monitor

        def local_fn(state, blocks, pn_params, vals, stim):
            blocks = {k: self._squeeze_blocks(v) for k, v in blocks.items()}
            state = state.__class__(
                neurons=state.neurons, spikes=state.spikes,
                prev_above=state.prev_above,
                syn=self._squeeze_syn(state.syn), t=state.t, key=state.key,
                finite=state.finite)
            gs = dict(zip(keys, vals))
            start = self._step_count(state)
            bufs0, caps = self._probe_init_local(n_steps)

            def body(carry, xs):
                i, stim_t = xs
                if mon is not None:
                    st, counts, bufs, hstate = carry
                else:
                    st, counts, bufs = carry
                st2, spk = self._local_step(st, blocks, pn_params, gs,
                                            stim=stim_t)
                counts = {k: counts[k] + spk[k] for k in counts}
                bufs = self._probe_write_local(bufs, caps, start, i, st2,
                                               spk, blocks)
                out = spk if record_raster else None
                if mon is not None:
                    with jax.named_scope("health"):
                        hstate = HE.accumulate(
                            mon, hstate, self._health_counts_local(spk),
                            self._health_ok_local(st2, blocks), self.dt,
                            self._pop_sizes)
                    return (st2, counts, bufs, hstate), out
                return (st2, counts, bufs), out

            counts0 = {name: jnp.zeros((self._shard[name],), jnp.int32)
                       for name in self.net.populations}
            xs = (jnp.arange(n_steps, dtype=jnp.int32),
                  stim if stim_keys else None)
            carry0 = (state, counts0, bufs0)
            if mon is not None:
                carry0 = carry0 + (HE.init_state(self._pop_sizes),)
            carry_out, raster = jax.lax.scan(body, carry0, xs,
                                             length=n_steps)
            st2, counts, bufs = carry_out[:3]
            pdata, pcounts = self._probe_finalize_local(bufs, caps, start,
                                                        n_steps)
            st2 = st2.__class__(
                neurons=st2.neurons, spikes=st2.spikes,
                prev_above=st2.prev_above,
                syn=self._unsqueeze_syn(st2.syn), t=st2.t, key=st2.key,
                finite=self._combine_finite(st2.finite))
            if mon is not None:
                hstate = HE.combine_across_devices(carry_out[3], self.axis)
                health = HE.finalize(mon, hstate, self.dt, self._pop_sizes)
                return st2, counts, raster, pdata, pcounts, health
            return st2, counts, raster, pdata, pcounts

        ax = self.axis
        counts_specs = {name: P(ax) for name in self.net.populations}
        raster_specs = ({name: P(None, ax) for name in self.net.populations}
                        if record_raster else None)
        pdata_specs, pcount_specs = self._probe_out_specs()
        out_specs = (self._state_specs, counts_specs, raster_specs,
                     pdata_specs, pcount_specs)
        if mon is not None:
            out_specs = out_specs + (
                HE.report_specs(self._pop_sizes, lambda: P()),)
        return self._shard_map(
            local_fn,
            in_specs=(*self._in_specs(), tuple(P() for _ in keys),
                      {k: P(None, ax) for k in stim_keys}),
            out_specs=out_specs)

    def run(self, n_steps: int,
            gscales: Optional[Mapping[str, jax.Array]] = None,
            state: Optional[SimState] = None,
            record_raster: bool = False,
            stim: Optional[Mapping[str, jax.Array]] = None) -> RunResult:
        """Scan n_steps under shard_map; spike statistics match the
        single-device Simulator bit for bit.  stim: population ->
        [n_steps, n] external currents (full-size; sliced per shard)."""
        fn, args, compiled = self._run_program(n_steps, gscales, state,
                                               record_raster, stim)
        with trace.span("run", model=self.net.name, n_steps=n_steps,
                        sharded=True, compile=compiled):
            out = fn(*args)
        st2, counts, raster, pdata, pcounts = out[:5]
        health = out[5] if self.monitor is not None else None
        pops = self.net.populations
        counts = {k: v[: pops[k].n] for k, v in counts.items()}
        t_sec = n_steps * self.dt * 1e-3
        rates = {k: jnp.mean(v) / t_sec for k, v in counts.items()}
        if record_raster:
            raster = {k: v[:, : pops[k].n] for k, v in raster.items()}
        rec = Recordings(data=self._crop_probe_data(pdata), counts=pcounts)
        return RunResult(state=st2, spike_counts=counts, rates_hz=rates,
                         finite=st2.finite,
                         raster=raster if record_raster else None,
                         recordings=rec, health=health)

    def _run_program(self, n_steps: int, gscales, state, record_raster,
                     stim):
        """The cached shard_map'd run program for these keys, its
        arguments, and whether this call created it."""
        gscales = dict(gscales or {})
        self._validate_gscales(gscales)
        self._validate_stim(stim)
        stim = self._pad_stim(stim or {})
        if state is None:
            state = self.init_state()
        keys = tuple(sorted(gscales))
        stim_keys = tuple(sorted(stim))
        cache_key = (n_steps, keys, record_raster, stim_keys)
        compiled = cache_key not in self._run_cache
        if compiled:
            self._run_cache[cache_key] = self._make_run(n_steps, keys,
                                                        record_raster,
                                                        stim_keys)
        vals = tuple(jnp.asarray(gscales[k], jnp.float32) for k in keys)
        args = (state, self._blocks, self._pn_params, vals, stim)
        return self._run_cache[cache_key], args, compiled

    def _make_step(self, keys: Tuple[str, ...],
                   stim_keys: Tuple[str, ...] = ()):
        def local_fn(state, blocks, pn_params, vals, stim):
            blocks = {k: self._squeeze_blocks(v) for k, v in blocks.items()}
            state = state.__class__(
                neurons=state.neurons, spikes=state.spikes,
                prev_above=state.prev_above,
                syn=self._squeeze_syn(state.syn), t=state.t, key=state.key,
                finite=state.finite)
            st2, spk = self._local_step(state, blocks, pn_params,
                                        dict(zip(keys, vals)), stim=stim)
            st2 = st2.__class__(
                neurons=st2.neurons, spikes=st2.spikes,
                prev_above=st2.prev_above,
                syn=self._unsqueeze_syn(st2.syn), t=st2.t, key=st2.key,
                finite=st2.finite)
            return st2, spk

        ax = self.axis
        return self._shard_map(
            local_fn,
            in_specs=(*self._in_specs(), tuple(P() for _ in keys),
                      {k: P(ax) for k in stim_keys}),
            out_specs=(self._state_specs,
                       {name: P(ax) for name in self.net.populations}))

    def step(self, state: SimState,
             gscales: Optional[Mapping[str, jax.Array]] = None,
             stim: Optional[Mapping[str, jax.Array]] = None):
        """One dt step (sharded); returns (new_state, spikes dict [n]).
        stim: population -> [n] external currents (full-size)."""
        gscales = dict(gscales or {})
        self._validate_gscales(gscales)
        self._validate_stim(stim)
        stim = self._pad_stim(stim or {})
        keys = tuple(sorted(gscales))
        stim_keys = tuple(sorted(stim))
        cache_key = (keys, stim_keys)
        if cache_key not in self._step_cache:
            self._step_cache[cache_key] = self._make_step(keys, stim_keys)
        vals = tuple(jnp.asarray(gscales[k], jnp.float32) for k in keys)
        st2, spk = self._step_cache[cache_key](state, self._blocks,
                                               self._pn_params, vals, stim)
        return st2, {k: v[: self.net.populations[k].n]
                     for k, v in spk.items()}

    def _make_sweep(self, n_steps: int, names: Tuple[str, ...]):
        def local_fn(state, blocks, pn_params, vals):
            blocks = {k: self._squeeze_blocks(v) for k, v in blocks.items()}
            state = state.__class__(
                neurons=state.neurons, spikes=state.spikes,
                prev_above=state.prev_above,
                syn=self._squeeze_syn(state.syn), t=state.t, key=state.key,
                finite=state.finite)

            start = self._step_count(state)

            def one(v):
                gs = {n: v for n in names}
                bufs0, caps = self._probe_init_local(n_steps)

                def body(carry, i):
                    st, counts, bufs = carry
                    st2, spk = self._local_step(st, blocks, pn_params, gs)
                    counts = {k: counts[k] + spk[k] for k in counts}
                    bufs = self._probe_write_local(bufs, caps, start, i,
                                                   st2, spk, blocks)
                    return (st2, counts, bufs), None

                counts0 = {name: jnp.zeros((self._shard[name],), jnp.int32)
                           for name in self.net.populations}
                (st2, counts, bufs), _ = jax.lax.scan(
                    body, (state, counts0, bufs0),
                    jnp.arange(n_steps, dtype=jnp.int32), length=n_steps)
                pdata, pcounts = self._probe_finalize_local(
                    bufs, caps, start, n_steps)
                return counts, st2.finite, pdata, pcounts

            counts, finite, pdata, pcounts = jax.vmap(one)(vals)
            return counts, self._combine_finite(finite), pdata, pcounts

        ax = self.axis
        pdata_specs, pcount_specs = self._probe_out_specs(lead=(None,))
        return self._shard_map(
            local_fn,
            in_specs=(*self._in_specs(), P()),
            out_specs=({name: P(None, ax)
                        for name in self.net.populations}, P(),
                       pdata_specs, pcount_specs))

    def sweep_gscale(self, names: Sequence[str], values, n_steps: int,
                     state: Optional[SimState] = None):
        """Vmapped gscale sweep inside shard_map: candidates on the batch
        dimension, neurons on the mesh.  Returns (values, rates, finite,
        counts, recordings) matching CompiledModel.sweep_gscale
        semantics (recordings leaves carry a leading candidate axis)."""
        names = tuple(names)
        self._validate_gscales({n: 1.0 for n in names})
        if state is None:
            state = self.init_state()
        values = jnp.atleast_1d(jnp.asarray(values, jnp.float32))
        cache_key = (tuple(names), n_steps)
        if cache_key not in self._sweep_cache:
            self._sweep_cache[cache_key] = self._make_sweep(n_steps, names)
        counts, finite, pdata, pcounts = self._sweep_cache[cache_key](
            state, self._blocks, self._pn_params, values)
        pops = self.net.populations
        counts = {k: v[:, : pops[k].n] for k, v in counts.items()}
        t_sec = n_steps * self.dt * 1e-3
        rates = {k: jnp.mean(v, axis=1) / t_sec for k, v in counts.items()}
        rec = Recordings(data=self._crop_probe_data(pdata), counts=pcounts)
        return values, rates, finite, counts, rec

    # ------------------------------------------------------------------
    # streaming / serving: a leading stream axis over independent sims
    # ------------------------------------------------------------------
    def _stream_state_specs(self):
        """Spec twin of a stream-batched SimState: every leaf gains a
        leading (unsharded) stream dim in front of its single-sim spec."""
        return jax.tree.map(lambda spec: P(None, *tuple(spec)),
                            self._state_specs)

    def init_stream_state(self, keys: jax.Array) -> SimState:
        """Batched sharded initial state: one independent simulation per
        slot, every leaf broadcast along a leading stream axis (neuron
        shards stay on their devices; per-slot PRNG keys replicated).  Slot
        s starts bit-identical to init_state(keys[s])."""
        keys = jnp.asarray(keys)
        S = int(keys.shape[0])
        base = self.init_state()
        mesh = self.mesh

        def bcast(x, spec):
            sh = NamedSharding(mesh, P(None, *tuple(spec)))
            return jax.device_put(
                jnp.broadcast_to(x[None], (S,) + x.shape), sh)

        st = jax.tree.map(bcast, base, self._state_specs)
        return SimState(
            neurons=st.neurons, spikes=st.spikes, prev_above=st.prev_above,
            syn=st.syn, t=st.t,
            key=jax.device_put(keys, self._sh["replicated"]),
            finite=st.finite)

    def select_streams(self, state: SimState, idx, keys) -> SimState:
        """Re-pack the stream axis between chunks (slot reclamation and
        elastic resize) — semantics match Simulator.select_streams: new
        slot j continues old slot ``idx[j]`` bit-for-bit, ``idx[j] < 0``
        fresh-inits from ``keys[j]``.  The stream axis is the *unsharded*
        leading axis (P(None, ...)), so the gather is device-local: neuron
        shards never move, and surviving slots stay bitwise identical on
        every device."""
        fresh = self.init_stream_state(jnp.asarray(keys))
        return _select_streams(state, fresh, jnp.asarray(idx, jnp.int32))

    def _make_serve(self, n_steps: int, keys: Tuple[str, ...],
                    stim_keys: Tuple[str, ...], record_raster: bool):
        mon = self.monitor

        def local_fn(state, blocks, pn_params, vals, stim, steps_left):
            blocks = {k: self._squeeze_blocks(v) for k, v in blocks.items()}
            gs = dict(zip(keys, vals))

            def one_stream(st, st_stim, left):
                st = st.__class__(
                    neurons=st.neurons, spikes=st.spikes,
                    prev_above=st.prev_above,
                    syn=self._squeeze_syn(st.syn), t=st.t, key=st.key,
                    finite=st.finite)
                start = self._step_count(st)
                bufs0, caps = self._probe_init_local(n_steps, serving=True)

                def body(carry, xs):
                    t_idx, stim_t = xs
                    if mon is not None:
                        st, counts, bufs, hstate = carry
                    else:
                        st, counts, bufs = carry
                    st2, spk = self._local_step(st, blocks, pn_params, gs,
                                                stim=stim_t)
                    act = t_idx < left
                    st2 = jax.tree.map(lambda a, b: jnp.where(act, a, b),
                                       st2, st)
                    spk = {k: v & act for k, v in spk.items()}
                    counts = {k: counts[k] + spk[k] for k in counts}
                    bufs = self._probe_write_local(bufs, caps, start,
                                                   t_idx, st2, spk,
                                                   blocks, gate=act)
                    out = spk if record_raster else None
                    if mon is not None:
                        with jax.named_scope("health"):
                            hstate = HE.accumulate(
                                mon, hstate, self._health_counts_local(spk),
                                self._health_ok_local(st2, blocks), self.dt,
                                self._pop_sizes, gate=act)
                        return (st2, counts, bufs, hstate), out
                    return (st2, counts, bufs), out

                counts0 = {name: jnp.zeros((self._shard[name],), jnp.int32)
                           for name in self.net.populations}
                xs = (jnp.arange(n_steps, dtype=jnp.int32),
                      st_stim if stim_keys else None)
                carry0 = (st, counts0, bufs0)
                if mon is not None:
                    carry0 = carry0 + (HE.init_state(self._pop_sizes),)
                carry_out, raster = jax.lax.scan(body, carry0, xs,
                                                 length=n_steps)
                st2, counts, bufs = carry_out[:3]
                pdata, pcounts = self._probe_finalize_local(
                    bufs, caps, start, jnp.minimum(left, n_steps),
                    serving=True)
                st2 = st2.__class__(
                    neurons=st2.neurons, spikes=st2.spikes,
                    prev_above=st2.prev_above,
                    syn=self._unsqueeze_syn(st2.syn), t=st2.t, key=st2.key,
                    finite=st2.finite)
                if mon is not None:
                    return st2, counts, raster, pdata, pcounts, carry_out[3]
                return st2, counts, raster, pdata, pcounts

            out = jax.vmap(one_stream)(state, stim, steps_left)
            st2, counts, raster, pdata, pcounts = out[:5]
            st2 = st2.__class__(
                neurons=st2.neurons, spikes=st2.spikes,
                prev_above=st2.prev_above, syn=st2.syn, t=st2.t,
                key=st2.key, finite=self._combine_finite(st2.finite))
            if mon is not None:
                # per-device NaN-guard verdicts merge on the batched
                # leaves (same pattern as the finite flag above); every
                # other health leaf is already replicated
                hstate = HE.combine_across_devices(out[5], self.axis)
                health = HE.finalize(mon, hstate, self.dt, self._pop_sizes)
                return st2, counts, raster, pdata, pcounts, health
            return st2, counts, raster, pdata, pcounts

        ax = self.axis
        stream_specs = self._stream_state_specs()
        counts_specs = {name: P(None, ax) for name in self.net.populations}
        raster_specs = ({name: P(None, None, ax)
                         for name in self.net.populations}
                        if record_raster else None)
        pdata_specs, pcount_specs = self._probe_out_specs(lead=(None,))
        out_specs = (stream_specs, counts_specs, raster_specs,
                     pdata_specs, pcount_specs)
        if mon is not None:
            out_specs = out_specs + (
                HE.report_specs(self._pop_sizes, lambda: P(None)),)
        return self._shard_map(
            local_fn,
            in_specs=(stream_specs, self._block_specs, self._pn_specs,
                      tuple(P() for _ in keys),
                      {k: P(None, None, ax) for k in stim_keys},
                      P()),
            out_specs=out_specs)

    def serve_chunk(self, state: SimState, stim: Mapping[str, jax.Array],
                    steps_left: jax.Array, n_steps: int,
                    gscales: Optional[Mapping[str, jax.Array]] = None,
                    record_raster: bool = False):
        """Advance every stream slot by up to n_steps under shard_map:
        streams on the vmap axis, neurons on the mesh.  Semantics match
        Simulator.serve_chunk (per-slot steps_left masking, masked lanes
        exact no-ops); outputs are cropped to real neurons.  Returns
        (state, counts, raster, recordings) with a leading stream axis on
        every recordings leaf — plus a per-slot HealthReport when the
        engine was built with a monitor."""
        gscales = dict(gscales or {})
        self._validate_gscales(gscales)
        self._validate_stim(stim)
        stim = self._pad_stim(stim)
        steps_left = jnp.asarray(steps_left, jnp.int32)
        keys = tuple(sorted(gscales))
        stim_keys = tuple(sorted(stim))
        cache_key = (n_steps, keys, stim_keys, record_raster)
        compiled = cache_key not in self._serve_cache
        if compiled:
            self._serve_cache[cache_key] = self._make_serve(
                n_steps, keys, stim_keys, record_raster)
        vals = tuple(jnp.asarray(gscales[k], jnp.float32) for k in keys)
        n_streams = int(jax.tree.leaves(state)[0].shape[0])
        with trace.span("serve_chunk", model=self.net.name,
                        n_steps=n_steps, streams=n_streams, sharded=True,
                        compile=compiled):
            out = self._serve_cache[cache_key](
                state, self._blocks, self._pn_params, vals, stim,
                steps_left)
        st2, counts, raster, pdata, pcounts = out[:5]
        pops = self.net.populations
        counts = {k: v[:, : pops[k].n] for k, v in counts.items()}
        if record_raster:
            raster = {k: v[:, :, : pops[k].n] for k, v in raster.items()}
        rec = Recordings(data=self._crop_probe_data(pdata), counts=pcounts)
        base = (st2, counts, (raster if record_raster else None), rec)
        if self.monitor is not None:
            return base + (out[5],)
        return base

    # ------------------------------------------------------------------
    # on-demand custom updates (one shard_map'd program per update name)
    # ------------------------------------------------------------------
    def custom_update(self, state: SimState, name: str) -> SimState:
        """Run one declared custom update on demand against a sharded
        state; reductions execute inside shard_map (psum/pmax across the
        mesh, per-post reductions device-local)."""
        if name not in self.custom_updates:
            raise ValueError(
                f"unknown custom update {name!r}; declared updates: "
                f"{sorted(self.custom_updates)}")
        if name not in self._custom_cache:
            cu = self.custom_updates[name]

            def local_fn(state, blocks, pn_params):
                blocks = {k: self._squeeze_blocks(v)
                          for k, v in blocks.items()}
                st = state.__class__(
                    neurons=state.neurons, spikes=state.spikes,
                    prev_above=state.prev_above,
                    syn=self._squeeze_syn(state.syn), t=state.t,
                    key=state.key, finite=state.finite)
                st2 = self._apply_custom_local(st, cu, jnp.bool_(True),
                                               blocks, pn_params)
                return st2.__class__(
                    neurons=st2.neurons, spikes=st2.spikes,
                    prev_above=st2.prev_above,
                    syn=self._unsqueeze_syn(st2.syn), t=st2.t, key=st2.key,
                    finite=self._combine_finite(st2.finite))

            self._custom_cache[name] = self._shard_map(
                local_fn, in_specs=self._in_specs(),
                out_specs=self._state_specs)
        return self._custom_cache[name](state, self._blocks,
                                        self._pn_params)

    def memory_report(self) -> List[dict]:
        """Per-group sharded footprint next to the paper's eq-(1)/(2)
        elements: what one device actually holds (connectivity block,
        dendritic-ring shard, dynamic state)."""
        D = self.n_shards
        out = []
        for g in self.net.synapses:
            rep = g.memory_report()
            blk = self._blocks[g.name]
            if "dense" in blk:
                local = int(blk["dense"].shape[1] * blk["dense"].shape[2])
            else:
                local = int(blk["g"].shape[1] * blk["g"].shape[2])
            rep["local_elements_per_device"] = local
            rep["ring_elements_per_device"] = (
                g.ring_slots * (self._npad[g.post] // D)
                if g.needs_ring else 0)
            rep["n_shards"] = D
            out.append(rep)
        return out
