"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload qwen2_5_3b.sessions --seed 7 \
        --seconds 51 --trace 0

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``bench/configs/<config>.json``, whose architecture is
``bench/arch/<arch>.py``) under a traffic mix
(``bench/traffic/<traffic>.json``), with the engine settings, offered
load and limits of ``bench/cells/<cell>.json``. The run:

1. checks the device (a TPU, as many chips as the cell asks for);
2. makes the weights on the device from ``--seed`` and builds the engine
   through the program's public API (``HostKVPool``, ``PrefillWorker``,
   ``DecodeWorker`` on the paged substrate, ``ServingLoop``);
3. warms every prefill shape, prefix-hit depth and decode width the
   cell's traffic can produce (set-up ends here);
4. offers the traffic to ``ServingLoop.submit`` from client threads for
   ``--seconds``, then drains what is in flight;
5. with ``--trace 1``, profiles the middle of the window and reads the
   per-layer metrics (``bench/metrics/<metric>.py``);
6. frees the engine and checks a sample of the served tokens against the
   float32 reference (``bench/reference.py``);
7. prints the result as the last line of standard output.

``--rate`` (a knee sweep) and ``--control`` (the fp8 control put in the
program's place: its tokens go through the same check, which they have
to fail) are for measuring the cell, never for its runs.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = ROOT / ".jax_cache"


def _setup_env() -> None:
    """Before JAX is imported: its persistent compilation cache lives at a
    fixed path inside the checkout (the program takes it from this
    variable), and every program is cached, small ones too."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_PROCESS:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_cell(workload: str) -> dict:
    """The cell's entry in ``BENCHMARK.json`` merged with its file."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = json.loads((BENCH / "cells" / f"{workload}.json").read_text())
    cell.update(name=workload, config=entry["config"],
                traffic=entry["traffic"], chips=entry["chips"])
    e2e = {m["name"] for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])}
    cell["end_to_end"] = sorted(e2e)
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if workload in m.get("workloads", [workload])
                         and m["moves"] in e2e]
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="offer this rate instead of the cell's (a knee "
                         "sweep, never a benchmark run)")
    ap.add_argument("--control", action="store_true",
                    help="check the fp8 control's tokens in place of the "
                         "served ones (measuring the limit, never a run)")
    args = ap.parse_args(argv)
    _setup_env()
    from bench.device import NoChip, check_devices
    cell = load_cell(args.workload)
    if args.rate is not None:
        cell["rate_rps"] = args.rate
    try:
        device = check_devices(cell["chips"])
    except NoChip as e:
        log(f"no chip: {e}")
        return 3
    from bench.harness import run_cell
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), device=device,
                      control=args.control, t_process=T_PROCESS, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
