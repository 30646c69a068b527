"""What one run recorded, as the per-layer metric readers see it."""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Optional

import numpy as np

import counts


@dataclasses.dataclass
class RunView:
    log: object                 # feed.RunLog
    window_s: float
    cfg: dict                   # configuration file
    peaks: dict
    stats0: dict                # engine stats() at the window's start
    stats1: dict                # ... and at its close
    events: Optional[list]      # obs trace events (traced runs), else None
    devtrace: dict              # devtrace.reduce() output ({} if none)
    setup_s: float

    def gaps_ms(self) -> list:
        return [(b - a) * 1e3 for t in self.log.tracks
                for a, b in zip(t.stamps, t.stamps[1:])
                if 0.0 <= b < self.window_s]

    def window_steps(self):
        return [s for s in self.log.steps if 0.0 <= s.t0 < self.window_s]

    def chunk_rows(self) -> dict:
        """Step start (microseconds, as the trace rounds it) -> [(start,
        take)] prefill chunks, from the
        engine's ``prefill_chunk`` trace events."""
        out = defaultdict(list)
        for ev in self.events or ():
            if ev.get("name") == "prefill_chunk":
                a = ev["args"]
                out[ev["ts"]].append((a["start"], a["take"]))
        return out

    def step_rows(self, step, chunks) -> list:
        return step.decode_rows + chunks.get(round(step.t0 * 1e6, 3), [])

    def request_events(self) -> dict:
        """rid -> {phase: first timestamp in seconds}."""
        out = defaultdict(dict)
        for ev in self.events or ():
            if ev.get("ph") == "i":
                out[ev["tid"]].setdefault(ev["name"], ev["ts"] / 1e6)
        return out

    def roofline_share(self, mixed: bool) -> Optional[float]:
        """Least time for the required work over measured time, in %, for
        the window's decode-only (``mixed=False``) or mixed steps."""
        if self.events is None:
            return None
        chunks = self.chunk_rows()
        least = spent = 0.0
        for s in self.window_steps():
            if s.mixed != mixed:
                continue
            f, b = counts.step_work(self.cfg, self.step_rows(s, chunks))
            least += counts.least_time(f, b, self.peaks)
            spent += s.t1 - s.t0
        return 100.0 * least / spent if spent > 0 else None


def percentile(xs, q) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else None
