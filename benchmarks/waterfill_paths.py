"""Time the XLA water-fill paths per fixed-point round across padded
shapes, on the chip, and check them against each other bit for bit.

``waterfill_plan`` picks the one-hot contraction (``"onehot"``) or the
scatter fixed point (``"xla"``) from a table's padded node count alone:
the contraction's cost per round grows with ``pf * pn``, the scatter's
with ``pf``. This script measures where they cross. Each call runs
exactly ``ROUNDS`` rounds (capacities drawn uniformly, so that no round
ends the fixed point early) over a table already on the device; the
time of the best of
``REPEATS`` calls, divided by ``ROUNDS``, is the round's time. Rates and
rounds of both paths are compared bit for bit at every shape, and once
at a fleet-like table that runs to its own end.

    python benchmarks/waterfill_paths.py [--out results.json]

Needs a TPU (a CPU run times XLA's CPU backend, which nobody deploys).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.kernels.swarm import ops  # noqa: E402

ROUNDS, REPEATS = 16, 3
SHAPES = [  # (pf, pn)
    (1 << 15, 1 << 15), (1 << 16, 1 << 15), (1 << 17, 1 << 15),
    (1 << 17, 1 << 17), (1 << 18, 1 << 17), (1 << 20, 1 << 17),
    (1 << 18, 1 << 18), (1 << 20, 1 << 18),
    (1 << 19, 1 << 19), (1 << 20, 1 << 19),
    (1 << 20, 1 << 20),
]


def table(pf: int, pn: int, rng, classes: bool = False):
    """A padded flow table of ``pf`` flows over ``pn`` nodes (the last
    one an origin), ready for ``ops._waterfill_jit``."""
    nf = pf - pf // 8  # padding as a table a little over a bucket has
    src = rng.integers(0, pn, nf)
    dst = rng.integers(0, pn - 1, nf)
    dst = np.where(dst == src, (dst + 1) % (pn - 1), dst)
    if classes:  # the fleet's client classes: few distinct levels
        up = rng.choice([25e6, 50e6], pn)
        dn = rng.choice([50e6, 100e6], pn)
    else:
        up = rng.uniform(1e6, 50e6, pn)
        dn = rng.uniform(1e6, 100e6, pn)
    up[-1] = 5e8
    s = np.full(pf, -1, np.int32)
    d = np.full(pf, -1, np.int32)
    s[:nf], d[:nf] = src, dst
    lk = np.zeros(pf, np.int32)  # every flow on the dummy link slot
    lc = np.zeros(128, np.float32)
    lc[0] = np.inf
    args = (s, d, lk, up.astype(np.float32), dn.astype(np.float32), lc)
    return [jax.device_put(a) for a in args]


def run(fn, args):
    rate, rounds = fn(*args)
    rate.block_until_ready()
    return np.asarray(rate), int(np.asarray(rounds)[0])


def timed(fn, args) -> float:
    run(fn, args)  # compile
    best = np.inf
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        run(fn, args)
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"waterfill_paths: no TPU (JAX found {dev.platform})")
    rng = np.random.default_rng(0)
    rows = []
    for pf, pn in SHAPES:
        tab = table(pf, pn, rng)
        row = {"pf": pf, "pn": pn}
        out = {}
        for impl in ("onehot", "xla"):
            fn = ops._waterfill_jit(ROUNDS, impl)
            row[f"{impl}_ms_per_round"] = 1e3 * timed(fn, tab) / ROUNDS
            out[impl] = run(fn, tab)
        row["rounds"] = out["xla"][1]
        row["bit_equal"] = bool(out["onehot"][1] == out["xla"][1]
                                and np.array_equal(out["onehot"][0],
                                                   out["xla"][0]))
        rows.append(row)
        print(json.dumps(row), flush=True)
    # a fleet-like table run to its own end: the same rounds and rates
    pf, pn = 1 << 17, 1 << 15
    tab = table(pf, pn, rng, classes=True)
    ends = {impl: run(ops._waterfill_jit(2 * pn + 2, impl), tab)
            for impl in ("onehot", "xla")}
    natural = {"pf": pf, "pn": pn, "rounds": ends["xla"][1],
               "bit_equal": bool(ends["onehot"][1] == ends["xla"][1]
                                 and np.array_equal(ends["onehot"][0],
                                                    ends["xla"][0]))}
    print(json.dumps(natural), flush=True)
    result = {"device": dev.device_kind, "rounds_per_call": ROUNDS,
              "shapes": rows, "natural": natural}
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(result, indent=1))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
