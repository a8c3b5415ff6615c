"""The records of a measured window: what an arrival process builds while it
drives the serving loop, and what the check and the metric readers read."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from bench.trace import Summary


@dataclass
class Request:
    params: Dict[str, int]
    due: float  # monotonic s (closed loop: when it was submitted)
    client: int = -1
    ticket: Any = None
    submitted: float = 0.0

    @property
    def done(self) -> Optional[float]:
        t = self.ticket
        return None if t is None or t.done_us is None else t.done_us * 1e-6

    @property
    def answered(self) -> bool:
        return self.ticket is not None and self.ticket.status == "done"

    @property
    def ok(self) -> bool:
        """Answered, undegraded and complete."""
        return (self.answered and self.ticket.result.degraded_backend is None
                and not self.ticket.result.overflow)


@dataclass
class Pump:
    start: float
    end: float
    served: List[Request]


@dataclass
class Drive:
    """What an arrival process's ``drive`` returns."""

    t0: float
    t_close: float  # end of the measured span
    t_end: float  # when the driver stopped (the traced span ends here)
    requests: List[Request]
    pumps: List[Pump]
    stalled: bool = False  # the loop stopped answering before the close


@dataclass
class Window:
    """What a metric reader sees of the measured window."""

    drive: Drive
    in_window: List[Request]  # the requests the window's metrics count
    missing: int  # requests of the window that never got an answer
    counters: Dict[str, int] = field(default_factory=dict)
    trace: Optional[Summary] = None

    @property
    def t0(self) -> float:
        return self.drive.t0

    @property
    def t_close(self) -> float:
        return self.drive.t_close

    @property
    def requests(self) -> List[Request]:
        return self.drive.requests

    @property
    def pumps(self) -> List[Pump]:
        return self.drive.pumps

    @property
    def finished(self) -> List[Request]:
        """Requests answered before the driver stopped."""
        return [r for r in self.drive.requests
                if r.done is not None and r.done <= self.drive.t_end]
