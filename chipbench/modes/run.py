"""Offline simulation: ``CompiledModel.run(steps, state=...)`` back to back,
each call continuing the previous call's state from the seed's initial
state.  The warm-up calls (``warmup_calls`` of the traffic file, one by
default) are the chain's first links.

The traffic's ``initial`` draws neuron variables of the initial state
from the seed, the same for the program and the reference:
``{"uniform": {"V": [lo, hi], ...}}`` sets each population's ``V`` to
independent uniforms on [lo, hi), ``{"normal": {"V": [mean, sd]}}`` to
independent normals.  A network started from its uniform
rest state fires in one synchronous burst and then falls silent for
some hundred steps; one started desynchronised runs in its steady
regime from the start."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench.check import Tally, over
from chipbench.harness import SPAN

NUMBERS = ("flip_margin", "spike_flips_ppm", "count_gap", "v_gap_mV",
           "finite_mismatch")
INITIAL_SALT = 0x1417
NORMAL_SALT = 0x4E


def initial_neurons(neurons: dict, rule: dict, seed: int) -> dict:
    """``neurons`` (pop -> {var: array}) with the variables that ``rule``
    names drawn from the seed; the draw depends on the seed, the
    population's name and order, the variable, the distribution and the
    size alone."""
    both = set(rule.get("uniform", {})) & set(rule.get("normal", {}))
    if both:
        raise ValueError(f"variables drawn twice: {sorted(both)}")
    key = jax.random.fold_in(jax.random.PRNGKey(seed), INITIAL_SALT)
    out = {}
    for i, pop in enumerate(sorted(neurons)):
        out[pop] = dict(neurons[pop])
        for kind, draw in (("uniform", _uniform), ("normal", _normal)):
            for j, (var, args) in enumerate(sorted(rule.get(kind,
                                                            {}).items())):
                x = neurons[pop][var]
                k = jax.random.fold_in(jax.random.fold_in(key, i), j)
                if kind == "normal":       # uniform keys predate normal
                    k = jax.random.fold_in(k, NORMAL_SALT)
                out[pop][var] = draw(k, x.shape, *map(float, args)).astype(
                    x.dtype)
    return out


def _uniform(key, shape, lo: float, hi: float):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def _normal(key, shape, mean: float, sd: float):
    return mean + sd * jax.random.normal(key, shape, jnp.float32)


class Mode:
    def __init__(self, model, config: dict, traffic: dict, seed: int):
        self.model = model
        self.pops = list(config["record_spikes"])
        self.config = config
        self.steps = int(traffic["steps_per_call"])
        self.warmup_calls = int(traffic.get("warmup_calls", 1))
        self.seed = seed
        self.initial = traffic.get("initial")
        self.state = None
        if model is not None:
            st = model.init_state(jax.random.PRNGKey(seed))
            if self.initial:
                st = dataclasses.replace(st, neurons=initial_neurons(
                    st.neurons, self.initial, seed))
            self.state = st
        self.links = []          # per call: raster, counts, neurons, finite
        self.timed = 0

    def _call(self) -> None:
        with jax.profiler.TraceAnnotation(SPAN + "dispatch"):
            res = self.model.run(self.steps, state=self.state)
        with jax.profiler.TraceAnnotation(SPAN + "fetch"):
            jax.block_until_ready(res)
        self.state = res.state
        self.links.append({
            "raster": {p: res.recordings.data[f"{p}_spikes"]
                       for p in self.pops},
            "counts": {p: res.spike_counts[p] for p in self.pops},
            "neurons": {p: res.state.neurons[p] for p in self.pops},
            "finite": res.finite})

    def warm_up(self) -> None:
        for _ in range(self.warmup_calls):
            self._call()

    def call(self) -> int:
        self._call()
        self.timed += 1
        return self.steps

    def spike_totals(self) -> dict:
        timed = self.links[len(self.links) - self.timed:]
        return {p: sum(int(link["counts"][p].sum()) for link in timed)
                for p in self.pops}

    def program_hlo(self) -> str:
        """The optimized HLO text of the program the calls ran (the
        compiled one: nothing is compiled again)."""
        return self.model.run_hlo(self.steps, state=self.state)

    def release(self) -> None:
        self.model = None
        self.state = None

    def control(self, ref, calls: int, dtype=jnp.bfloat16) -> None:
        """Fill the chain from the reference computed in ``dtype``,
        free-running, in the program's place."""
        net = ref.build(self.config["model_config"], self.seed, dtype)
        st = self._ref_initial(ref, net)
        for _ in range(calls):
            st, dec, _, overflow = ref.simulate(net, st, self.steps)
            if overflow:
                raise RuntimeError("control overflowed its capacity")
            self.links.append({
                "raster": dec, "counts": {p: d.sum(axis=0)
                                          for p, d in dec.items()},
                "neurons": ref.neurons(st, net), "finite": st["finite"]})

    def _ref_initial(self, ref, net) -> dict:
        """The reference's initial state, with the traffic's draws."""
        st = ref.init_state(net, jax.random.PRNGKey(self.seed))
        if self.initial:
            st = ref.sync(st, net, initial_neurons(
                ref.neurons(st, net), self.initial, self.seed))
        return st

    def check(self, ref, limits: dict):
        """Each call through the reference, teacher-forced by its recorded
        spikes.  The first starts from the reference's own initial state,
        each later one from the membranes the program handed it (which the
        call before was checked on), so a gap measures one call's drift
        whatever the chain's length.  Returns (numbers, calls checked,
        calls failed)."""
        net = ref.build(self.config["model_config"], self.seed)
        st = self._ref_initial(ref, net)
        total, failed = Tally(NUMBERS), 0
        for i, link in enumerate(self.links):
            if i:
                st = ref.sync(st, net, self.links[i - 1]["neurons"])
            one = Tally(NUMBERS)
            st, dec, margin, overflow = ref.simulate(
                net, st, self.steps, teacher=link["raster"])
            mine = ref.neurons(st, net)
            one.spikes_vs(dec, margin, link["raster"])
            one.counts_vs(link["counts"], dec)
            one.v_vs({p: link["neurons"][p]["V"] for p in self.pops},
                     {p: mine[p]["V"] for p in self.pops})
            one.finite_vs(link["finite"], st["finite"])
            total.add(one)
            failed += int(overflow or over(one.numbers(), limits))
        return total.numbers(), len(self.links), failed
