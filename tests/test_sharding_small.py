"""Sharding rules + a small-mesh dry-run in a subprocess (8 fake devices so
the main test process keeps its single-device view)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest
from jax.sharding import PartitionSpec as P

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_param_spec_rules_unit():
    """Rule allocation on synthetic leaves (no mesh devices needed beyond 1
    -- use the real helper with a fake mesh namespace)."""
    from repro.launch import sharding as SH

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 4}

    # moe w_gate [E=8, d=16, f=32]: E not divisible by 4? 8%4==0 -> expert
    spec = SH._alloc((8, 16, 32), ["model", "fsdp", "model"], FakeMesh())
    assert spec == P("model", "data", None)
    # mixtral-like E=6 (not divisible) -> ffn gets the model axis
    spec = SH._alloc((6, 16, 32), ["model", "fsdp", "model"], FakeMesh())
    assert spec == P(None, "data", "model")
    # stacked dense mlp [L, d, f]: stack dim never sharded
    spec = SH._alloc((5, 16, 32), ["fsdp", "model"], FakeMesh())
    assert spec == P(None, "data", "model")
    # non-divisible dims dropped
    spec = SH._alloc((7, 9), ["fsdp", "model"], FakeMesh())
    assert spec == P(None, None)


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import sys
    sys.path.insert(0, {src!r})
    from repro.configs import ARCHS, reduced
    from repro.launch import sharding as SH
    from repro.models import transformer as T
    from repro.models import model as M
    from repro.configs.base import ShapeConfig

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    results = {{}}
    for arch in ["qwen2-0.5b", "granite-moe-1b-a400m", "mamba2-2.7b"]:
        cfg = dataclasses.replace(reduced(ARCHS[arch]), d_model=256,
                                  vocab=1024, n_kv=2)
        shape = ShapeConfig("t", 64, 8, "train")
        with SH.activate(mesh):
            ps = jax.eval_shape(lambda: T.init_params(
                cfg, jax.random.PRNGKey(0)))
            pshard = SH.spec_tree_to_shardings(
                SH.param_specs(ps, mesh), mesh)
            specs = M.input_specs(cfg, shape)
            bshard = SH.spec_tree_to_shardings(
                SH.batch_specs(specs["batch"], mesh), mesh)
            def loss(p, b):
                return T.loss_fn(p, cfg, b)[0]
            lowered = jax.jit(loss, in_shardings=(pshard, bshard)).lower(
                ps, specs["batch"])
            compiled = lowered.compile()
            txt = compiled.as_text()
        results[arch] = {{
            "compiled": True,
            "has_collectives": ("all-reduce" in txt or
                                 "all-gather" in txt),
        }}
    print(json.dumps(results))
""")


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess():
    code = _SUBPROCESS.format(src=SRC)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for arch, r in res.items():
        assert r["compiled"], arch
        assert r["has_collectives"], arch


def test_compile_cache_follows_env_else_fixed_checkout_dir(monkeypatch,
                                                           tmp_path):
    """Entry points keep the persistent compile cache where
    JAX_COMPILATION_CACHE_DIR says, setting no path of their own; without
    it, in one fixed directory of the checkout."""
    from repro.launch import compile_cache as CC

    was = jax.config.jax_compilation_cache_dir
    was_meta = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        monkeypatch.setenv(CC.CACHE_ENV, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert CC.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None   # JAX reads it

        monkeypatch.delenv(CC.CACHE_ENV)
        path = CC.enable_compile_cache()
        assert path == str(CC.DEFAULT_CACHE_DIR) == CC.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert Path(path).parent == Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          was_meta)
