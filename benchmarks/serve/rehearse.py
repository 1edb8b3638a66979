"""Compile each configuration's decode step, and the reference pass that
checks it, for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 benchmarks/serve/rehearse.py

For every configuration in ``BENCHMARK.json``: the program's
``serve_step`` at the served batch and cache, as ``Server`` jits it, and
the float32 reference the configuration names over a whole cache of
positions, with the weights its leaf tree defines.  It
prints each program's ``memory_analysis``; what the chip's compiler
would refuse (a program that does not fit, a kernel Mosaic rejects) is
refused here.
"""

import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.launch.steps import make_serve_step
    from repro.models import get_model
    from servebench.harness import arch_config
    from servebench.modelspec import load_spec
    from servebench.spec import load_benchmark, load_reference
    from servebench.weights import model_leaves

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    def report(what, compiled):
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"  {what}: arguments {m.argument_size_in_bytes} B, output "
              f"{m.output_size_in_bytes} B, temp {m.temp_size_in_bytes} B, "
              f"alias {m.alias_size_in_bytes} B, total {total} B",
              flush=True)

    for c in load_benchmark(ROOT)["configs"]:
        spec = load_spec(ROOT / c["file"])
        model = get_model(arch_config(spec))
        params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        cache = placed(jax.eval_shape(
            lambda: model.init_cache(spec.slots, spec.max_len)))
        tokens = jax.ShapeDtypeStruct((spec.slots, 1), jnp.int32,
                                      sharding=chip)
        print(f"{spec.name}:", flush=True)
        report("serve_step", jax.jit(make_serve_step(model)).lower(
            params, cache, tokens).compile())

        reference = load_reference(spec.raw, ROOT)
        tree = model_leaves(spec)
        ours = placed({k: jax.ShapeDtypeStruct(v[0], jnp.dtype(v[1]))
                       for k, v in tree["top"].items()}
                      | {"layers": {k: jax.ShapeDtypeStruct(
                          v[0], jnp.dtype(v[1])) for k, v in
                          tree["layers"].items()}})
        rows = jax.ShapeDtypeStruct((spec.slots, spec.max_len), jnp.int32,
                                    sharding=chip)
        for quant in (False, True):
            fn = jax.jit(lambda p, r, q=quant: reference._hidden(
                spec, p, r, q))
            with jax.default_matmul_precision("highest"):
                report(f"reference {'control ' if quant else ''}"
                       f"hidden pass over {spec.max_len} positions",
                       fn.lower(ours, rows).compile())


if __name__ == "__main__":
    main()
