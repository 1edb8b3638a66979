"""Distribution layer: banking bridge, pipeline parallelism (subprocess
with a forced multi-device CPU), mini dry-run integration."""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.parallel import sharding as shd


def test_bankable_bridge():
    assert shd.bankable(8192, 16)
    assert shd.bankable(102400, 16)
    assert shd.bankable(64, 16)
    assert not shd.bankable(8, 16)        # fewer heads than lanes
    assert not shd.bankable(51865, 16)    # non-divisible vocab
    assert shd.bankable(240, 16)


def test_param_specs_roles():
    from repro.configs import get_arch
    from repro.models import get_model
    import dataclasses

    cfg = dataclasses.replace(get_arch("deepseek_67b"), n_layers=2)
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    # fake a 16-wide model axis by asking bankable directly: use specs from
    # the production shape via a mesh-shaped namespace
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    specs = shd.param_specs(shapes, FakeMesh(), fsdp=False)
    assert specs["embed"] == jax.sharding.PartitionSpec("model", None)
    assert specs["layers"]["wq"][-1] == "model"
    assert specs["layers"]["wo"][1] == "model"
    assert specs["layers"]["w_down"][1] == "model"


def _run_subprocess(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_pipeline_matches_sequential():
    """4-stage GPipe == sequential layer application (subprocess: 4 devs)."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.pipeline import pipeline_apply

        S, L_per, M, mb, D = 4, 2, 8, 4, 16
        mesh = jax.make_mesh((S,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.normal(size=(S, L_per, D, D)) * 0.2, jnp.float32)

        def stage_fn(params, x):  # params (L_per, D, D)
            def body(h, w):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, params)
            return h

        x = jnp.asarray(rng.normal(size=(M, mb, D)), jnp.float32)
        got = pipeline_apply(stage_fn, Ws, x, mesh, axis="stage")
        # sequential reference
        h = x
        for s in range(S):
            h = jax.vmap(lambda xi: stage_fn(Ws[s], xi))(h)
        np.testing.assert_allclose(np.asarray(got), np.asarray(h),
                                   atol=1e-5)
        print("PIPELINE-OK")
    """)


@pytest.mark.slow
def test_mini_dryrun_multipod():
    """End-to-end dry-run on a (2,2,2) mini multi-pod mesh (subprocess)."""
    out = _run_subprocess("""
        import os
        import dataclasses, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        import repro.launch.dryrun as dr
        import repro.configs as C

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        base = C.get_arch("whisper_base")
        cfg = dataclasses.replace(base.reduced(), n_heads=4, n_kv_heads=2)
        C_get = C.get_arch
        dr.get_arch = lambda n: cfg
        shape = dataclasses.replace(dr.SHAPES["train_4k"], seq_len=64,
                                    global_batch=8)
        dr.SHAPES = dict(dr.SHAPES); dr.SHAPES["train_4k"] = shape
        r = dr.lower_cell("whisper_base", "train_4k", mesh)
        assert r["flops"] > 0
        print("DRYRUN-OK", r["compile_s"])
    """, devices=8)
    assert "DRYRUN-OK" in out


@pytest.mark.parametrize("variant", ["baseline", "moe_a2a"])
def test_mini_dryrun_moe_decode(variant):
    """An MoE decode cell lowers on a (2, 2) mesh: its cache specs follow
    the MoE cache's fields (subprocess: 4 devices)."""
    out = _run_subprocess(f"""
        import dataclasses, jax
        import repro.launch.dryrun as dr
        import repro.configs as C

        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = C.get_arch("olmoe_1b_7b").reduced()
        dr.get_arch = lambda n: cfg
        dr.SHAPES = dict(dr.SHAPES, decode_32k=dataclasses.replace(
            dr.SHAPES["decode_32k"], seq_len=64, global_batch=4))
        r = dr.lower_cell("olmoe_1b_7b", "decode_32k", mesh,
                          variant="{variant}")
        assert r["flops"] > 0
        print("DRYRUN-OK")
    """)
    assert "DRYRUN-OK" in out


def test_logical_axis_cache_specs():
    from repro.configs import get_arch
    from repro.configs.base import SHAPES

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    specs = shd.cache_specs(get_arch("zamba2_2_7b"), SHAPES["decode_32k"],
                            FakeMesh())
    assert specs.k[3] == "model"  # 32 kv heads shard over 16
    specs_long = shd.cache_specs(get_arch("gemma3_12b"), SHAPES["long_500k"],
                                 FakeMesh())
    assert specs_long.k[2] == ("data", "model")  # seq spread over all axes
