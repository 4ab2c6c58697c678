import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step with AdamW for
train shapes; prefill_step; serve_step = one decode against a full-length KV
cache), lowers it against ShapeDtypeStruct inputs with the production
shardings, compiles it, and records:

  * memory_analysis()       -- proves the cell fits (plus analytic bytes/dev)
  * cost_analysis()         -- HLO flops / bytes for the roofline
  * collective bytes        -- parsed from the post-SPMD HLO text, per
                               collective kind (all-gather, all-reduce,
                               reduce-scatter, all-to-all, collective-permute)

Artifacts land in experiments/dryrun/<mesh>/<arch>__<shape>.json; the
roofline benchmark and EXPERIMENTS.md tables read them.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import json
import math
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, get_shape
from repro.launch import sharding as SH
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.models import model as M
from repro.models import transformer as T
from repro.optim import adamw

ART_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|c64|c128)\[([0-9,]*)\]")


def _bytes_of_shape(txt: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(txt):
        dt, dims = m.groups()
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in post-SPMD HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.lstrip()
        # "%x = TYPE all-gather(...)" or fusion-less "x = ... all-gather("
        m = re.search(r"=\s+(.+?)\s+(all-gather|all-reduce|reduce-scatter|"
                      r"all-to-all|collective-permute)(-start)?\(", s)
        if not m:
            continue
        kind = m.group(2)
        # operand bytes: shapes inside the call parens are not printed, but
        # the RESULT type is; for all-gather result >= operand, for
        # reduce-scatter result <= operand.  Parse operand shapes from the
        # result-type prefix: for these ops HLO prints the full signature
        # in the type slot, e.g. "bf16[8,128]{1,0}" or a tuple.
        type_txt = m.group(1)
        nbytes = _bytes_of_shape(type_txt)
        if kind == "all-gather":
            # operand = result / group size; group size parsed from
            # replica_groups if present on the line
            g = _group_size(s)
            nbytes = nbytes // max(1, g)
        elif kind == "all-reduce":
            pass  # operand size == result size
        out[kind] += nbytes
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return 1


def analytic_param_bytes_per_device(params_struct, specs, mesh) -> int:
    """Sum leaf bytes / shards — works even if memory_analysis() is bare."""
    total = 0
    for leaf, spec in zip(jax.tree.leaves(params_struct),
                          jax.tree.leaves(
                              specs, is_leaf=lambda x: isinstance(x, P))):
        shards = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                shards *= mesh.shape[a]
        total += leaf.size * leaf.dtype.itemsize // shards
    return int(total)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def make_train_step(cfg):
    """Train step with optional gradient accumulation (cfg.microbatches):
    big-model train cells don't fit their activations at the full global
    batch (see EXPERIMENTS.md §Dry-run memory column); accumulation bounds
    live activations to one microbatch at the cost of re-gathering FSDP
    weights per microbatch."""
    ocfg = adamw.AdamWConfig(lr=3e-4)
    n_mb_cfg = max(1, getattr(cfg, "microbatches", 1))

    def lf(p, b):
        loss, metrics = T.loss_fn(p, cfg, b)
        return loss, metrics

    def _effective_mb(global_batch: int) -> int:
        # keep each microbatch divisible by the batch-axis extent, or the
        # batch dim stops sharding and activations replicate (e.g. zamba2
        # at mb=16 on the 32-wide multi-pod batch axes)
        from repro.models.layers import _AXIS_ENV
        shards = max(1, _AXIS_ENV.get("batch_size", 1))
        mb = min(n_mb_cfg, global_batch)
        while mb > 1 and ((global_batch % mb)
                          or (global_batch // mb) % shards):
            mb -= 1
        return mb

    def train_step(params, opt_state, batch):
        n_mb = _effective_mb(
            jax.tree.leaves(batch)[0].shape[0])
        if n_mb == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lf, has_aux=True)(params, batch)
        else:
            mbs = jax.tree.map(
                lambda x: x.reshape((n_mb, x.shape[0] // n_mb)
                                    + x.shape[1:]), batch)

            def body(carry, mb):
                gsum, lsum = carry
                (loss, metrics), g = jax.value_and_grad(
                    lf, has_aux=True)(params, mb)
                gsum = jax.tree.map(
                    lambda a, b_: a + b_.astype(jnp.float32), gsum, g)
                return (gsum, lsum + loss), metrics

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), ms = T.maybe_scan(body, (g0, jnp.float32(0.0)),
                                            mbs)
            grads = jax.tree.map(lambda g: g / n_mb, gsum)
            loss = lsum / n_mb
            metrics = jax.tree.map(lambda m: m[-1], ms)
        new_params, new_state, om = adamw.update(ocfg, grads, opt_state,
                                                 params)
        return new_params, new_state, {"loss": loss, **metrics, **om}

    return train_step


def make_prefill_step(cfg):
    def prefill_step(params, tokens, extra):
        return T.prefill(params, cfg, tokens, extra)
    return prefill_step


def make_serve_step(cfg):
    def serve_step(params, caches, token):
        return T.decode_step(params, cfg, caches, token)
    return serve_step


# ---------------------------------------------------------------------------
# roofline extrapolation lowerings
# ---------------------------------------------------------------------------
# cost_analysis counts while-loop bodies once, so scanned layer stacks and
# chunked attention under-report.  We re-lower at depth-groups g=1 and g=2
# with layer scans python-unrolled and naive (fully-counted) attention, then
# extrapolate linearly: term(G_full) = t1 + (t2 - t1) * (G_full - 1).
# The roofline builder swaps naive-attention terms for analytic flash terms.

def _roofline_lowering(cfg, shape, mesh, g: int) -> dict:
    import dataclasses as _dc

    from repro import flags
    from repro.configs.base import depth_scaled

    # microbatches=1 for the measurement: per-step totals are identical
    # (same global batch), and unrolling mb x layers would blow up compile;
    # the full-depth compile keeps accumulation for the memory proof.
    dcfg = _dc.replace(depth_scaled(cfg, g), microbatches=1)
    old_unroll = T.UNROLL_LAYERS
    T.UNROLL_LAYERS = True
    flags.ROOFLINE_NAIVE_ATTN = True
    try:
        with SH.activate(mesh):
            params_struct = jax.eval_shape(
                lambda: T.init_params(dcfg, jax.random.PRNGKey(0)))
            pshard = SH.spec_tree_to_shardings(
                SH.param_specs(params_struct, mesh), mesh)
            specs = M.input_specs(dcfg, shape)
            if shape.kind == "train":
                step = make_train_step(dcfg)
                opt_struct = jax.eval_shape(
                    lambda p: adamw.init(adamw.AdamWConfig(), p),
                    params_struct)
                oshard = adamw.AdamWState(
                    NamedSharding(mesh, P()),
                    SH.spec_tree_to_shardings(
                        SH.param_specs(opt_struct.mu, mesh), mesh),
                    SH.spec_tree_to_shardings(
                        SH.param_specs(opt_struct.nu, mesh), mesh),
                    SH.spec_tree_to_shardings(
                        SH.param_specs(opt_struct.master, mesh), mesh))
                bshard = SH.spec_tree_to_shardings(
                    SH.batch_specs(specs["batch"], mesh), mesh)
                lowered = jax.jit(
                    step, in_shardings=(pshard, oshard, bshard),
                    out_shardings=(pshard, oshard, None)).lower(
                    params_struct, opt_struct, specs["batch"])
            elif shape.kind == "prefill":
                step = make_prefill_step(dcfg)
                tok = specs["tokens"]
                extra = {k: v for k, v in specs.items() if k != "tokens"}
                if dcfg.serve_replicate_weights:
                    pshard = jax.tree.map(
                        lambda _: NamedSharding(mesh, P()), pshard)
                lowered = jax.jit(step, in_shardings=(
                    pshard,
                    SH.spec_tree_to_shardings(SH.batch_specs(tok, mesh),
                                              mesh),
                    SH.spec_tree_to_shardings(SH.batch_specs(extra, mesh),
                                              mesh))).lower(
                    params_struct, tok, extra)
            else:
                step = make_serve_step(dcfg)
                caches = specs["caches"]
                cshard = SH.cache_shardings(caches, mesh)
                if dcfg.serve_replicate_weights:
                    pshard = jax.tree.map(
                        lambda _: NamedSharding(mesh, P()), pshard)
                lowered = jax.jit(
                    step, in_shardings=(pshard, cshard,
                                        NamedSharding(mesh, P())),
                    out_shardings=(None, cshard)).lower(
                    params_struct, caches, specs["token"])
            compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        return {
            "g": g,
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
            "collectives": coll,
        }
    finally:
        T.UNROLL_LAYERS = old_unroll
        flags.ROOFLINE_NAIVE_ATTN = False


def roofline_terms(cfg, shape, mesh) -> dict:
    from repro.configs.base import full_groups
    g_full = full_groups(cfg)
    t1 = _roofline_lowering(cfg, shape, mesh, 1)
    t2 = _roofline_lowering(cfg, shape, mesh, min(2, g_full))
    span = max(1, t2["g"] - t1["g"])

    def extrap(a, b):
        return a + (b - a) / span * (g_full - t1["g"])

    coll1 = t1["collectives"]["bytes"]
    coll2 = t2["collectives"]["bytes"]
    return {
        "g_full": g_full,
        "depth1": t1, "depth2": t2,
        "flops": extrap(t1["flops"], t2["flops"]),
        "bytes": extrap(t1["bytes"], t2["bytes"]),
        "transcendentals": extrap(t1["transcendentals"],
                                  t2["transcendentals"]),
        "collective_bytes": {
            k: extrap(coll1[k], coll2[k]) for k in coll1},
        "collective_total": extrap(t1["collectives"]["total_bytes"],
                                   t2["collectives"]["total_bytes"]),
    }


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = cfg.applicable(shape)
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "timestamp": time.time()}
    if not ok:
        rec.update(status="SKIP", reason=why)
        _save(rec, save)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        with SH.activate(mesh):
            params_struct = jax.eval_shape(
                lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
            pspecs = SH.param_specs(params_struct, mesh)
            pshard = SH.spec_tree_to_shardings(pspecs, mesh)

            specs = M.input_specs(cfg, shape)
            if shape.kind == "train":
                step = make_train_step(cfg)
                opt_struct = jax.eval_shape(
                    lambda p: adamw.init(adamw.AdamWConfig(), p),
                    params_struct)
                oshard = (
                    NamedSharding(mesh, P()),
                    SH.spec_tree_to_shardings(
                        SH.param_specs(opt_struct.mu, mesh), mesh),
                    SH.spec_tree_to_shardings(
                        SH.param_specs(opt_struct.nu, mesh), mesh),
                    SH.spec_tree_to_shardings(
                        SH.param_specs(opt_struct.master, mesh), mesh),
                )
                oshard = adamw.AdamWState(*oshard)
                bshard = SH.spec_tree_to_shardings(
                    SH.batch_specs(specs["batch"], mesh), mesh)
                jf = jax.jit(step,
                             in_shardings=(pshard, oshard, bshard),
                             out_shardings=(pshard, oshard, None))
                lowered = jf.lower(params_struct, opt_struct,
                                   specs["batch"])
            elif shape.kind == "prefill":
                step = make_prefill_step(cfg)
                tok = specs["tokens"]
                extra = {k: v for k, v in specs.items() if k != "tokens"}
                tshard = SH.spec_tree_to_shardings(
                    SH.batch_specs(tok, mesh), mesh)
                eshard = SH.spec_tree_to_shardings(
                    SH.batch_specs(extra, mesh), mesh)
                if cfg.serve_replicate_weights:
                    pshard = jax.tree.map(
                        lambda _: NamedSharding(mesh, P()), pshard)
                out_struct = jax.eval_shape(step, params_struct, tok,
                                            extra)
                cshard_out = SH.cache_shardings(out_struct[1], mesh)
                jf = jax.jit(step, in_shardings=(pshard, tshard, eshard),
                             out_shardings=(None, cshard_out))
                lowered = jf.lower(params_struct, tok, extra)
            else:  # decode / long
                step = make_serve_step(cfg)
                caches = specs["caches"]
                cshard = SH.cache_shardings(caches, mesh)
                tokshard = NamedSharding(mesh, P())
                if cfg.serve_replicate_weights:
                    # tiny models: TP all-reduce latency dwarfs the matmuls;
                    # replicate weights, keep only batch sharding (§Perf)
                    pshard = jax.tree.map(
                        lambda _: NamedSharding(mesh, P()), pshard)
                jf = jax.jit(step,
                             in_shardings=(pshard, cshard, tokshard),
                             out_shardings=(None, cshard),
                             donate_argnums=(1,))
                lowered = jf.lower(params_struct, caches, specs["token"])

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        n_dev = mesh.devices.size

        mem_rec = {}
        for field in ("generated_code_size_in_bytes",
                      "argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(mem, field, None)
            if v is not None:
                mem_rec[field] = int(v)
        cost_rec = {}
        if cost:
            for k in ("flops", "bytes accessed", "transcendentals",
                      "optimal_seconds"):
                if k in cost:
                    cost_rec[k] = float(cost[k])
            for k, v in cost.items():
                if k.startswith("bytes accessed"):
                    cost_rec[k] = float(v)

        t1 = time.time()
        try:
            roof = roofline_terms(cfg, shape, mesh)
        except Exception as e:  # noqa: BLE001
            roof = {"error": f"{type(e).__name__}: {e}"}
        t_roof = time.time() - t1

        rec.update(
            status="OK",
            n_devices=n_dev,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            roofline_s=round(t_roof, 2),
            memory_analysis=mem_rec,
            cost_analysis=cost_rec,
            collectives=coll,
            roofline=roof,
            analytic_param_bytes_per_device=analytic_param_bytes_per_device(
                params_struct, pspecs, mesh),
            hlo_bytes=len(hlo),
        )
        print(f"[OK] {arch} x {shape_name} x {mesh_tag}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
              f"flops={cost_rec.get('flops', 0):.3e} "
              f"coll={coll['total_bytes']:.3e}B")
    except Exception as e:  # noqa: BLE001 - record and continue
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[FAIL] {arch} x {shape_name} x {mesh_tag}: {e}")
    _save(rec, save)
    return rec


def _save(rec: dict, save: bool) -> None:
    if not save:
        return
    d = ART_DIR / rec["mesh"]
    d.mkdir(parents=True, exist_ok=True)
    path = d / f"{rec['arch']}__{rec['shape']}.json"
    path.write_text(json.dumps(rec, indent=1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = ([s.name for s in SHAPES] if (args.all or not args.shape)
              else [args.shape])
    for mp in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        tag = "pod2x16x16" if mp else "pod16x16"
        path = ART_DIR / tag / f"{a}__{s}.json"
        if args.skip_existing and path.exists():
            try:
                prev = json.loads(path.read_text())
                if prev.get("status") in ("OK", "SKIP"):
                    print(f"[skip-existing] {a} x {s} x {tag}")
                    continue
            except json.JSONDecodeError:
                pass
        rec = run_cell(a, s, mp)
        if rec["status"] == "FAIL":
            failures += 1
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
