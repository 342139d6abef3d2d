"""Benchmark harness: timing, throughput, and MFU statistics.

Reference parity: thunder/benchmarks/__init__.py (`Benchmark:72`, timing
machinery `_benchmark:238`) and the LitGPT end-to-end metrics of
benchmark_litgpt.py:348-367 — `average_iter_time`, `tokens_per_sec`
(= global_batch × seq_len / iter_time), `model_flop_per_sec` (→ MFU against
chip peak), `memory_used_GB`.

TPU notes: timing forces completion with a scalar device→host read (async
dispatch otherwise returns immediately, see bench.py), and peak memory
comes from the device's allocator stats where exposed.
"""

from __future__ import annotations

import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import numpy as np

# Published bf16 peaks per chip, TFLOP/s (Google Cloud TPU documentation, the
# "TPU v5e", "TPU v5p", "TPU v4" and "TPU v6e" system-architecture pages).
# The one table of peaks: bench.py and analysis/cost.py look the chip up here.
TPU_PEAK_BF16_TFLOPS = {"v5e": 197.0, "v5p": 459.0, "v4": 275.0, "v6e": 918.0}

# jax's ``device_kind`` (lower-cased, spaces dropped) -> generation; the
# first match wins, so "v5lite" is tested before "v5".
_DEVICE_KINDS = (("v5lite", "v5e"), ("v5e", "v5e"), ("v6lite", "v6e"), ("v6e", "v6e"),
                 ("v5p", "v5p"), ("v5", "v5p"), ("v4", "v4"))


def tpu_generation(device_kind: Optional[str] = None) -> str:
    """Generation name ("v5e", ...) of ``device_kind`` (default: the first
    device jax reports). A device that is not in the table raises: a peak
    that is guessed makes every utilization wrong."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower().replace(" ", "")
    if "tpu" in kind:
        for needle, gen in _DEVICE_KINDS:
            if needle in kind:
                return gen
    raise ValueError(
        f"no peak is recorded for device_kind {device_kind!r}; known TPU generations: "
        f"{sorted(TPU_PEAK_BF16_TFLOPS)} (add the chip to thunder_tpu/benchmarks/__init__.py)"
    )


def peak_tflops(device_kind: Optional[str] = None) -> float:
    return TPU_PEAK_BF16_TFLOPS[tpu_generation(device_kind)]


def device_description() -> dict:
    """The device as jax reports it — what every result line names."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def device_memory_used_gb() -> Optional[float]:
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
        return stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0)) / 1e9
    except Exception:
        return None


def force_completion(out) -> float:
    """Force device completion via a scalar host read; returns the scalar."""
    import jax

    from thunder_tpu.core.pytree import tree_leaves

    for leaf in reversed(tree_leaves(out)):
        if isinstance(leaf, jax.Array):
            flat = leaf.reshape(-1) if leaf.ndim else leaf
            return float(np.asarray(flat[0] if leaf.ndim else flat))
    return 0.0


@dataclass
class BenchmarkResult:
    name: str
    iters: int
    times_s: list[float]
    tokens_per_iter: Optional[int] = None
    flops_per_iter: Optional[float] = None
    memory_gb: Optional[float] = None
    # True when the run was async-dispatched with one final sync: times_s
    # then holds the amortized average repeated, so per-iter variance was
    # NOT measured and summary() omits the synthetic stats.
    pipelined: bool = False

    @property
    def pruned_times_s(self) -> list[float]:
        """Outlier-pruned samples (reference: benchmarks/__init__.py:220-455
        prunes timing outliers before reporting): drop points beyond
        1.5×IQR of the quartiles. With <4 samples nothing is pruned."""
        ts = sorted(self.times_s)
        if len(ts) < 4:
            return ts
        q1 = float(np.percentile(ts, 25))
        q3 = float(np.percentile(ts, 75))
        lo, hi = q1 - 1.5 * (q3 - q1), q3 + 1.5 * (q3 - q1)
        pruned = [t for t in ts if lo <= t <= hi]
        return pruned or ts

    @property
    def outliers(self) -> int:
        return len(self.times_s) - len(self.pruned_times_s)

    @property
    def median_s(self) -> float:
        return statistics.median(self.pruned_times_s)

    @property
    def mean_s(self) -> float:
        return statistics.fmean(self.pruned_times_s)

    @property
    def stdev_s(self) -> float:
        ts = self.pruned_times_s
        return statistics.stdev(ts) if len(ts) > 1 else 0.0

    def percentile_s(self, q: float) -> float:
        return float(np.percentile(self.pruned_times_s, q))

    @property
    def tokens_per_sec(self) -> Optional[float]:
        return self.tokens_per_iter / self.median_s if self.tokens_per_iter else None

    @property
    def tflops_per_sec(self) -> Optional[float]:
        return self.flops_per_iter / self.median_s / 1e12 if self.flops_per_iter else None

    @property
    def mfu(self) -> Optional[float]:
        """Model FLOP/s over the chip's peak; None (not measured) off the
        TPU: a CPU run has no device utilization to report. A TPU that is
        not in the table raises."""
        t = self.tflops_per_sec
        if not t or device_description()["platform"] != "tpu":
            return None
        return t / peak_tflops()

    def summary(self) -> dict:
        d = {
            "name": self.name,
            "iters": self.iters,
            "average_iter_time_s": round(self.mean_s, 5),
        }
        if self.pipelined:
            d["pipelined"] = True  # one sync; per-iter variance not measured
        else:
            d["median_iter_time_s"] = round(self.median_s, 5)
            d["stdev_s"] = round(self.stdev_s, 6)
            d["p25_s"] = round(self.percentile_s(25), 5)
            d["p75_s"] = round(self.percentile_s(75), 5)
            if self.iters >= 10:
                d["p90_s"] = round(self.percentile_s(90), 5)
            if self.outliers:
                d["outliers_pruned"] = self.outliers
        if self.tokens_per_sec:
            d["tokens_per_sec"] = round(self.tokens_per_sec)
        if self.tflops_per_sec:
            d["model_tflop_per_sec"] = round(self.tflops_per_sec, 2)
            mfu = self.mfu
            d["mfu"] = round(mfu, 4) if mfu is not None else None  # None: not measured
        if self.memory_gb is not None:
            d["memory_used_GB"] = round(self.memory_gb, 2)
        return d


def run_benchmark(
    name: str,
    fn: Callable[[], Any],
    *,
    warmup: int = 2,
    iters: int = 5,
    tokens_per_iter: Optional[int] = None,
    flops_per_iter: Optional[float] = None,
    pipelined: bool = False,
) -> BenchmarkResult:
    """``pipelined=True`` dispatches all iterations asynchronously and syncs
    once at the end (no per-iteration host sync in the timed window).
    Per-iter times then all equal the amortized average."""
    for _ in range(warmup):
        force_completion(fn())
    if pipelined:
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn()
        force_completion(out)
        avg = (time.perf_counter() - t0) / iters
        times = [avg] * iters
    else:
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            force_completion(fn())
            times.append(time.perf_counter() - t0)
    return BenchmarkResult(
        name=name,
        iters=iters,
        times_s=times,
        tokens_per_iter=tokens_per_iter,
        flops_per_iter=flops_per_iter,
        memory_gb=device_memory_used_gb(),
        pipelined=pipelined,
    )


def training_flops_per_token(n_params: float) -> float:
    """fwd+bwd ≈ 6·N FLOPs/token (fwd 2N, bwd 4N)."""
    return 6.0 * n_params


def forward_flops_per_token(n_params: float) -> float:
    return 2.0 * n_params


def count_params(params) -> int:
    from thunder_tpu.core.pytree import tree_leaves

    return sum(int(np.prod(p.shape)) for p in tree_leaves(params) if hasattr(p, "shape"))
