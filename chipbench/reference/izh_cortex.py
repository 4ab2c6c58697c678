"""Plain reference of the Izhikevich (2003) cortical network.

Izhikevich, "Simple model of spiking neurons", IEEE TNN 14:1569 (2003), as
the GeNN paper's section 5.1 runs it: a 4:1 excitatory:inhibitory
population, each neuron with ``n_conn`` distinct random targets over the
whole population, excitatory weights U(0, 0.5), inhibitory U(0, -1),
thalamic input 5 N(0,1) (excitatory) and 2 N(0,1) (inhibitory) per step,
two half-steps on V per dt.

Everything comes from the seed through ``jax.random`` with the draw
schedule the network is declared with (one key per row, folded from the
seed), so this module rebuilds the same graph without reading it from the
simulator.  Propagation gathers the spiking rows and scatter-adds them in
float32; ``dtype=jnp.bfloat16`` gives the lower-precision control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MAX_REDRAW_ROUNDS = 64
THRESHOLD = 29.99
V_PEAK = 30.0
V0, U0 = -65.0, -13.0
EXC_W, INH_W = 0.5, -1.0
EXC_NOISE, INH_NOISE = 5.0, 2.0
# key-schedule constants of the declared construction: targets, weights
TARGET_SALT, WEIGHT_SALT = 0xC0, 0x5EED


def sizes(cfg: dict) -> tuple[int, int, int]:
    n = int(cfg["n_total"])
    n_exc = int(round(n * float(cfg["exc_frac"])))
    return n, n_exc, n - n_exc


def layout(cfg: dict) -> dict:
    """Populations and synapse populations with their sizes, for the
    necessary-work counters."""
    n, n_exc, n_inh = sizes(cfg)
    k = int(cfg["n_conn"])
    return {
        "populations": {"exc": {"n": n_exc, "model": "izhikevich"},
                        "inh": {"n": n_inh, "model": "izhikevich"}},
        # every pre neuron reaches k targets; Pulse synapses keep no state
        "synapses": [{"pre": "exc", "fanout": k, "psm_vars": 0, "n_post": n},
                     {"pre": "inh", "fanout": k, "psm_vars": 0, "n_post": n}],
    }


def _row_keys(key, n_rows):
    return jax.vmap(lambda r: jax.random.fold_in(key, r))(
        jnp.arange(n_rows, dtype=jnp.int32))


def _dup_mask(s):
    return jnp.concatenate([jnp.zeros((1,), bool), s[1:] == s[:-1]])


def _targets_redraw(rk, n_post, k):
    """k distinct targets: draw k, redraw the duplicates until none."""
    def cond(c):
        i, _, dup = c
        return dup & (i < MAX_REDRAW_ROUNDS)

    def body(c):
        i, vals, _ = c
        fresh = jax.random.randint(jax.random.fold_in(rk, i), (k,), 0,
                                   n_post, jnp.int32)
        vals = jnp.sort(jnp.where(_dup_mask(vals), fresh, vals))
        return i + 1, vals, _dup_mask(vals).any()

    v0 = jnp.sort(jax.random.randint(jax.random.fold_in(rk, 0), (k,), 0,
                                     n_post, jnp.int32))
    return jax.lax.while_loop(cond, body, (1, v0, _dup_mask(v0).any()))[1]


def _targets_topk(rk, n_post, k):
    """k distinct targets as the k smallest of n_post uniforms."""
    u = jax.random.uniform(rk, (n_post,))
    return jnp.sort(jax.lax.top_k(-u, k)[1].astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_pre", "n_post", "k", "hi",
                                             "dtype"))
def _rows(key, n_pre, n_post, k, hi, dtype):
    tkey = jax.random.fold_in(key, TARGET_SALT)
    one = _targets_topk if k > n_post // 2 else _targets_redraw
    post = jax.vmap(lambda rk: one(rk, n_post, k))(_row_keys(tkey, n_pre))
    wkey = jax.random.fold_in(key, WEIGHT_SALT)
    g = jax.vmap(lambda rk: 0.0 + hi * jax.random.uniform(rk, (k,)))(
        _row_keys(wkey, n_pre))
    # zero-weight slots up to whole 128-lane tiles: flattening an unaligned
    # slot axis compiles for minutes on a TPU at 10^8 slots; +0.0 changes
    # no sum
    pad = ((0, 0), (0, -k % 128))
    return jnp.pad(post, pad), jnp.pad(g, pad).astype(dtype)


def build(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    n, n_exc, n_inh = sizes(cfg)
    k = int(cfg["n_conn"])
    key = jax.random.PRNGKey(seed)
    ke, ki = jax.random.split(jax.random.split(key)[0])
    re = jax.random.uniform(ke, (n_exc,))
    ri = jax.random.uniform(ki, (n_inh,))
    params = {
        "a": jnp.concatenate([jnp.full((n_exc,), 0.02), 0.02 + 0.08 * ri]),
        "b": jnp.concatenate([jnp.full((n_exc,), 0.2), 0.25 - 0.05 * ri]),
        "c": jnp.concatenate([-65.0 + 15.0 * re ** 2,
                              jnp.full((n_inh,), -65.0)]),
        "d": jnp.concatenate([8.0 - 6.0 * re ** 2, jnp.full((n_inh,), 2.0)]),
    }
    rows = {}
    for sidx, (pre, n_pre, hi) in enumerate((("exc", n_exc, EXC_W),
                                             ("inh", n_inh, INH_W))):
        rows[pre] = _rows(jax.random.fold_in(key, sidx), n_pre, n, k, hi,
                          jnp.dtype(dtype).name)
    return {"n": n, "n_exc": n_exc, "dt": float(cfg["dt"]),
            "noise": float(cfg.get("input_scale", 1.0)),
            "params": {p: v.astype(dtype) for p, v in params.items()},
            "rows": rows, "dtype": jnp.dtype(dtype)}


def init_state(net: dict, key) -> dict:
    n, dt = net["n"], net["dtype"]
    return {"V": jnp.full((n,), V0, dt), "U": jnp.full((n,), U0, dt),
            "spikes": jnp.zeros((n,), bool), "key": key,
            "finite": jnp.ones((), bool)}


def simulate(net: dict, state: dict, n_steps: int, gscales=None,
             teacher=None, capacity: int | None = None):
    """Advance ``n_steps``.  With ``teacher`` (pop -> [n_steps, n] bool)
    propagation and resets follow the teacher's spikes, and each step's own
    threshold decision is returned beside them.  Returns (state, decisions
    and margins pop -> [n_steps, n], overflow): a margin is the membrane's
    distance above threshold before the peak clamp; ``overflow`` is True
    when a step had more spiking rows than ``capacity`` (the result is then
    not valid)."""
    gs = {p: float((gscales or {}).get(p, 1.0)) for p in ("exc", "inh")}
    n_exc = net["n_exc"]
    if capacity is None:
        capacity = net["n"] if teacher is None else _teacher_capacity(teacher)
    forced = None
    if teacher is not None:
        forced = jnp.concatenate([jnp.asarray(teacher["exc"]),
                                  jnp.asarray(teacher["inh"])], axis=1)
    run = _simulate_jit(n_steps, gs["exc"], gs["inh"], int(capacity),
                        teacher is not None, net["dt"], net["noise"],
                        net["dtype"].name)
    state, dec, margin, over = run(net["params"], net["rows"], state, forced)
    split = lambda a: {"exc": a[:, :n_exc], "inh": a[:, n_exc:]}
    return state, split(dec), split(margin), bool(over)


def _teacher_capacity(teacher) -> int:
    """Rows to gather per step: the teacher's most spikes in a step,
    rounded up to a power of two so that few programs are compiled."""
    most = max(int(np.asarray(v).sum(axis=1).max(initial=0))
               for v in teacher.values())
    return max(128, 1 << max(most - 1, 0).bit_length())


@functools.lru_cache(maxsize=None)
def _simulate_jit(n_steps, gs_exc, gs_inh, capacity, forced, dt, noise,
                  dtype_name):
    dtype = jnp.dtype(dtype_name)

    def prop(rows, spikes_pre):
        post, g = rows
        n_pre = post.shape[0]
        (idx,) = jnp.nonzero(spikes_pre, size=min(capacity, n_pre),
                             fill_value=n_pre)
        live = idx < n_pre
        safe = jnp.minimum(idx, n_pre - 1)
        w = jnp.where(live[:, None], g[safe], jnp.zeros((), dtype))
        over = jnp.sum(spikes_pre.astype(jnp.int32)) > capacity
        return post[safe].reshape(-1), w.reshape(-1), over

    @jax.jit
    def run(params, rows, state, teacher):
        n = state["V"].shape[0]
        n_exc = rows["exc"][0].shape[0]
        c = lambda x: jnp.asarray(x, dtype)

        def step(carry, t_spikes):
            st, over = carry
            key, k_in_e, _, k_in_i, _ = jax.random.split(st["key"], 5)
            isyn = jnp.zeros((n,), dtype)
            for pre, lo, hi, gs in (("exc", 0, n_exc, gs_exc),
                                    ("inh", n_exc, n, gs_inh)):
                post, w, o = prop(rows[pre], st["spikes"][lo:hi])
                acc = jnp.zeros((n,), dtype).at[post].add(w)
                isyn = isyn + c(gs) * acc
                over = over | o
            ext = jnp.concatenate([
                (EXC_NOISE * noise) * jax.random.normal(k_in_e, (n_exc,)),
                (INH_NOISE * noise) * jax.random.normal(k_in_i,
                                                        (n - n_exc,))])
            i_in = isyn + ext.astype(dtype)
            v, u = st["V"], st["U"]
            hdt = c(0.5 * dt)
            for _ in range(2):
                v = v + hdt * (c(0.04) * v * v + c(5.0) * v + c(140.0) - u
                               + i_in)
            u = u + c(dt) * params["a"] * (params["b"] * v - u)
            margin = (v - c(THRESHOLD)).astype(jnp.float32)
            v = jnp.minimum(v, c(V_PEAK))
            decision = v >= c(THRESHOLD)
            spk = decision if t_spikes is None else t_spikes
            v = jnp.where(spk, params["c"], v)
            u = jnp.where(spk, u + params["d"], u)
            finite = (st["finite"] & jnp.all(jnp.isfinite(v))
                      & jnp.all(jnp.isfinite(u)))
            new = {"V": v, "U": u, "spikes": spk, "key": key,
                   "finite": finite}
            return (new, over), (decision, margin)

        (state, over), (dec, margin) = jax.lax.scan(
            step, (state, jnp.zeros((), bool)),
            teacher if forced else None, length=n_steps)
        return state, dec, margin, over

    return run


def neurons(state: dict, net: dict) -> dict:
    """pop -> {var: array}: the state the comparison reads."""
    n_exc = net["n_exc"]
    return {"exc": {"V": state["V"][:n_exc], "U": state["U"][:n_exc]},
            "inh": {"V": state["V"][n_exc:], "U": state["U"][n_exc:]}}


def sync(state: dict, net: dict, neurons: dict) -> dict:
    """The state with its membranes set to ``neurons`` (pop -> {var})."""
    dt = net["dtype"]
    out = dict(state)
    for var in ("V", "U"):
        out[var] = jnp.concatenate([jnp.asarray(neurons["exc"][var], dt),
                                    jnp.asarray(neurons["inh"][var], dt)])
    return out
