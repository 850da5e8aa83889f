"""MFU accounting — model FLOPs utilization vs chip peak, and the
steady-state throughput window behind ``tokens_per_sec``/``mfu`` in
``Trainer.fit`` (docs/training_performance.md)."""

from __future__ import annotations

import time

import jax

# peak dense bf16 FLOP/s per chip, keyed by a substring of the device's
# ``device_kind`` (Google Cloud TPU documentation, per-generation pages)
PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5": 459e12,       # v5p
    "v5p": 459e12,
    "v6 lite": 918e12,  # trillium / v6e
    "v6e": 918e12,
}
# CPU meshes (tests, rehearsals) keep the ``mfu`` field's arithmetic
# defined against a nominal 1 TFLOP/s — a placeholder, not a device peak
CPU_NOMINAL_FLOPS = 1e12


def chip_peak_flops(device=None) -> float:
    """Peak of ``device``'s chip. A TPU whose ``device_kind`` is not in
    the table is an error, not a default."""
    device = device or jax.devices()[0]
    kind = device.device_kind.lower()
    for name, peak in PEAK_FLOPS.items():
        if name in kind:
            return peak
    if device.platform == "cpu":
        return CPU_NOMINAL_FLOPS
    raise ValueError(
        f"no peak FLOP/s known for device kind '{device.device_kind}' "
        f"(platform '{device.platform}') — add it to PEAK_FLOPS with "
        "its source")


def mfu(tokens_per_sec: float, flops_per_token: float,
        n_chips: int | None = None, device=None) -> float:
    n_chips = n_chips or jax.device_count()
    peak = chip_peak_flops(device) * n_chips
    value = (tokens_per_sec * flops_per_token) / peak
    # surface the last computed utilization on /metrics so a scrape
    # answers "is this slice earning its keep" without a log dive
    from ..obs import TRAIN_MFU

    TRAIN_MFU.set(value)
    return value


class ThroughputTracker:
    """Steady-state tokens/sec window for the training loop.

    Dividing total tokens by total elapsed time folds the first step's
    XLA compile into the rate, understating throughput (and MFU) for any
    run short enough to care about — a 60 s compile over a 100-step smoke
    run halves the reported number. The tracker excludes the first
    ``warmup_excluded`` steps from the window: ``note_step`` is called
    after each step's *dispatch* returns (jit tracing+compile block the
    host there, execution does not), so the steady window starts once
    compile-class host stalls are behind us. Compile time itself is
    reported separately (``compile_seconds``).
    """

    def __init__(self, warmup_excluded: int = 1):
        self.warmup_excluded = max(0, int(warmup_excluded))
        self.steps = 0
        self.tokens_total = 0
        self._t_start = time.perf_counter()
        self._t_steady: float | None = (
            self._t_start if self.warmup_excluded == 0 else None)
        self._tokens_at_steady = 0

    def note_step(self, tokens: int):
        self.steps += 1
        self.tokens_total += int(tokens)
        if self._t_steady is None and self.steps >= self.warmup_excluded:
            self._t_steady = time.perf_counter()
            self._tokens_at_steady = self.tokens_total

    @property
    def in_steady_state(self) -> bool:
        return (self._t_steady is not None
                and self.tokens_total > self._tokens_at_steady)

    def tokens_per_sec(self) -> float:
        """Steady-state rate; falls back to the whole-run rate while the
        warmup window hasn't produced a measurable steady interval."""
        now = time.perf_counter()
        if self.in_steady_state:
            elapsed = now - self._t_steady
            tokens = self.tokens_total - self._tokens_at_steady
        else:
            elapsed = now - self._t_start
            tokens = self.tokens_total
        return tokens / elapsed if elapsed > 0 else 0.0
