"""What a reader is given."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class MetricContext:
    cell: object        # spec.Cell: configuration, workload, family
    peaks: dict         # this device's row of peaks.json
    result: dict        # the driver's: window_s, summary, records, counters
    trace: dict | None  # trace_reduce.reduce_dir's, or None

    def program(self, prefix: str) -> dict | None:
        """Count and device seconds of the traced programs whose name starts
        with ``prefix`` (``jit_decode``), or None where none ran."""
        if not self.trace:
            return None
        hits = [p for name, p in self.trace["programs"].items() if name.startswith(prefix)]
        if not hits or sum(p["count"] for p in hits) == 0:
            return None
        return {"count": sum(p["count"] for p in hits),
                "total_s": sum(p["total_s"] for p in hits)}
