"""Observability overhead gate — ``python -m repro.obs.overhead``.

Two checks over an NVCache stack on a modelled SSD, both on the same
workload: one writer issuing 4 KiB ``pwrite`` + ``fsync`` at random
page-aligned offsets (fio's psync engine with ``fsync=1``).

* **Overhead**: µs per op plain (``obs_level=0``) against fully
  instrumented (``obs_level=2``).  Plain and instrumented runs are
  interleaved back to back and the gate takes the median of the per-pair
  overheads: a pair shares the machine's noise phase (CPU frequency,
  co-tenant load), and the median discards the pairs a hiccup landed on.
  Each op's cost is dominated by the deterministic modelled device time,
  the deployment-realistic denominator.  Limit: at most
  :data:`MAX_OVERHEAD_PCT`.  (``obs_level=0`` must be free: that guard is
  the tracemalloc test in ``tests/test_obs.py``.)
* **Span coverage**: at ``obs_level=2`` the foreground spans (each op's
  ``write.op_us`` plus the fsync drain-barrier stalls ``stall.barrier_us``)
  must add up to the workload's wall clock; what they fail to cover is
  the loop's own overhead (rng, timestamps).  Limit: the ratio lies in
  :data:`COVERAGE`.

Exits non-zero when the report breaks either limit.  Device costs are
modelled sleeps (:class:`repro.storage.tiers.CostGate`) scaled by
:data:`SCALE`, so the interpreter's cost per op keeps roughly the ratio to
the device's that the paper's hardware has; the numbers are host-clock
readings of this modelled stack, not device metrics.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from repro.core import NVCache, Policy
from repro.core.policy import MIB
from repro.storage import tiers
from repro.storage.fsapi import NVCacheFS

MAX_OVERHEAD_PCT = 10.0          # obs_level=2 against plain, median pair
COVERAGE = (0.9, 1.1)            # foreground span seconds over wall seconds
SCALE = 20.0                     # modelled device time multiplier
LOG_MIB = 2.0
BS = 4096


def make_fs(obs_level: int) -> NVCacheFS:
    """NVCache with a 2 MiB log of 4 KiB entries over an SSD-class tier
    behind the kernel page cache (``sync=False``)."""
    policy = Policy(entry_size=BS, log_entries=int(LOG_MIB * MIB) // BS,
                    page_size=BS, read_cache_pages=1024, batch_min=1000,
                    batch_max=10000, verify_crc=False, obs_level=obs_level)
    tier = tiers.Tier(tiers.SSD_SATA, sync=False, scale=SCALE)
    return NVCacheFS(NVCache(policy, tier))


def random_write(fs, *, total_mib: float, file_mib: float) -> float:
    """``fsync=1`` random writes from one thread, at seeded offsets;
    returns the mean µs per op."""
    fd = fs.open("/fio.dat")
    rng = np.random.default_rng(11)
    n_ops = int(total_mib * MIB) // BS
    n_slots = max(1, int(file_mib * MIB) // BS)
    buf = b"x" * BS
    lat_s = 0.0
    for _ in range(n_ops):
        off = int(rng.integers(0, n_slots)) * BS
        t0 = time.perf_counter()
        fs.pwrite(fd, buf, off)
        fs.fsync(fd)
        lat_s += time.perf_counter() - t0
    return 1e6 * lat_s / max(1, n_ops)


def gate_us_per_op(obs_level: int, *, total_mib: float = 1.0) -> float:
    fs = make_fs(obs_level)
    try:
        return random_write(fs, total_mib=total_mib, file_mib=1.0)
    finally:
        fs.nv.shutdown()


def span_coverage(*, total_mib: float = 3.0) -> dict:
    """Foreground span seconds at ``obs_level=2`` over the wall clock of
    the same loop."""
    fs = make_fs(2)
    try:
        t0 = time.perf_counter()
        random_write(fs, total_mib=total_mib, file_mib=2.0)
        wall_s = time.perf_counter() - t0
        m = fs.nv.metrics()
    finally:
        fs.nv.shutdown()
    fg_s = (m["write.op_us"]["sum_us"] + m["stall.barrier_us"]["sum_us"]) * 1e-6
    return {"wall_s": wall_s, "foreground_span_s": fg_s,
            "span_coverage_ratio": fg_s / max(1e-12, wall_s)}


def measure(*, repeats: int = 5, gate_mib: float = 1.0,
            span_mib: float = 3.0) -> dict:
    """The gate's report: ``repeats`` interleaved plain/instrumented pairs
    and one span-coverage run."""
    plain, full, pairs = [], [], []
    for _ in range(repeats):
        p_us = gate_us_per_op(0, total_mib=gate_mib)
        f_us = gate_us_per_op(2, total_mib=gate_mib)
        plain.append(p_us)
        full.append(f_us)
        pairs.append(100.0 * (f_us - p_us) / max(1e-12, p_us))
    return {"overhead_pct": sorted(pairs)[len(pairs) // 2],
            "overhead_pct_pairs": pairs,
            "samples_us_plain": plain,
            "samples_us_obs2": full,
            **span_coverage(total_mib=span_mib)}


def check(report: dict) -> list:
    """The limits the report breaks, as messages; empty when it passes."""
    failures = []
    if report["overhead_pct"] > MAX_OVERHEAD_PCT:
        failures.append(f"obs_level=2 costs {report['overhead_pct']:.1f}% "
                        f"> {MAX_OVERHEAD_PCT:.0f}%")
    lo, hi = COVERAGE
    cov = report["span_coverage_ratio"]
    if not lo <= cov <= hi:
        failures.append(f"span totals drifted from wall-clock: {cov:.3f} "
                        f"outside [{lo}, {hi}]")
    return failures


def main() -> int:
    report = measure()
    print(json.dumps(report, indent=2))
    print(f"median overhead: {report['overhead_pct']:+.1f}%  "
          f"span coverage: {100 * report['span_coverage_ratio']:.1f}% "
          f"of wall-clock")
    failures = check(report)
    for msg in failures:
        print("FAIL:", msg, file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
