"""What one run leaves for the metric readers in ``perfbench/metrics``.

A reader is ``perfbench/metrics/<metric name>.py`` with ``read(run)``: it
returns the metric's value, or None where the run holds nothing for it
(the harness then leaves the metric out of the line).
"""

from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

from perfbench import roofline, tracefile
from perfbench.driver import Window, decode_positions

METRICS = Path(__file__).resolve().parent / "metrics"
DECODE_PROGRAM = "jit__decode_fn"      # ServingEngine._decode_fn under jit
PREFILL_PROGRAM = "jit__prefill_fn"    # ServingEngine._prefill_fn under jit


@dataclass
class RunData:
    cfg: dict
    window: Window
    setup_s: float
    memory_peak_bytes: int
    device_kind: str
    trace: Optional[tracefile.Trace] = None

    @property
    def peak(self) -> dict:
        return roofline.peaks(self.device_kind)

    @property
    def window_s(self) -> float:
        return self.window.close - self.window.open

    # ---- the traced part of the window ---------------------------------

    def traced_ns(self) -> tuple[int, int]:
        return tracefile.window(self.trace)

    def traced_s(self) -> float:
        t0, t1 = self.traced_ns()
        return (t1 - t0) / 1e9

    def in_traced(self, t: float) -> bool:
        """Whether engine-clock time ``t`` lies in the traced part."""
        a, b = self.window.traced
        return a <= t < b

    def decode_steps(self) -> list[list[int]]:
        """Slot positions of each decode step called in the traced part."""
        e = self.cfg["engine"]
        return [pos for t, pos in decode_positions(
            self.window.ticks, e["decode_steps_per_tick"], e["s_max"])
            if self.in_traced(t)]

    def prefill_batches(self) -> list[list[int]]:
        """Real prompt lengths of each prefill batch in the traced part."""
        return [lens for t, lens in self.window.batches if self.in_traced(t)]

    def program_runs(self, name: str, device: int = 0):
        t0, t1 = self.traced_ns()
        return tracefile.runs(self.trace.modules[device], name, t0, t1)


def reader(name: str):
    """The ``read`` function of metric ``name``."""
    path = METRICS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
