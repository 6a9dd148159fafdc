"""What one run leaves for the metric readers: the traffic driver's timed
calls, the reference's tally, set-up and memory, and the reduced profiler
trace."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class RunRecord:
    cell: str
    cfg: dict
    traffic: dict
    driver: object               # the cell's Driver after its window
    setup_s: float
    memory_peak_bytes: int
    tally: object                # harness.reference.Tally
    records: object = None       # harness.trace.Records (--trace 1)
    reduction: object = None     # harness.trace.Reduction (--trace 1)

    @property
    def calls(self) -> list:
        return self.driver.calls
