"""Run one cell of the on-chip serving benchmark once.

    python3 benchmarks/serve/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1> [--control 1]

From the root of a checkout, on a machine whose first JAX device is a
TPU.  ``--workload`` names a cell of ``BENCHMARK.json``; its
configuration, traffic mix and per-layer metric readers are found by
name under ``benchmarks/serve/``.  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
from a profiler trace of the window.  ``--control 1`` puts the float8
control of the correctness comparison in the program's place: such a run
reads ``correct`` false.

The last line of standard output is one JSON object; the numbers the
comparison decided ``correct`` by are the last lines of standard error
and the last key of that object.  Off a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def fail(msg: str) -> None:
    print(f"serve benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be a non-negative whole number")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program is not in this checkout ({ROOT / 'src'})")
    if not (ROOT / "BENCHMARK.json").is_file():
        fail(f"no BENCHMARK.json at {ROOT}")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))

    from servebench.modelspec import load_spec
    from servebench.peaks import peaks_for
    from servebench.runcell import check_lines, run_cell
    from servebench.spec import load_benchmark, resolve
    from servebench.traffic import load_mix

    cell = resolve(load_benchmark(ROOT), args.workload, ROOT)
    spec = load_spec(cell.config_file)
    mix = load_mix(cell.mix_file)

    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

    # the cache lives in this checkout, whatever the environment points at
    os.environ[ENV_VAR] = str(ROOT / ".jax_cache")
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devices[0].platform!r} "
             f"({devices[0].device_kind}); this benchmark only runs on a "
             f"TPU")
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, JAX sees "
             f"{len(devices)}")
    peaks = peaks_for(devices[0].device_kind)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"{cell.name}: seed {args.seed}, {args.seconds:g} s window, "
          f"trace {args.trace}; {device['kind']} x{device['count']}; "
          f"compile cache {cache}", file=sys.stderr, flush=True)
    result, info = run_cell(cell, spec, mix, args.seed, args.seconds,
                            bool(args.trace), bool(args.control), peaks,
                            T_START, device)
    for line in info + check_lines(result["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
