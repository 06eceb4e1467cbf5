"""Runner kinds: each module has ``run(ctx) -> Evidence``. A traffic mix's
``runner`` key names the module."""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass
class RunContext:
    cell: dict[str, Any]
    config: dict[str, Any]
    traffic: dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    device: dict[str, Any]
    #: perf_counter at process start: set-up is measured from here
    t_process: float
    #: profile the device in a traced run. Only the CPU tests turn it off:
    #: their backend has no device plane to reduce.
    profile_device: bool = True

    @property
    def profiled(self) -> bool:
        return self.trace and self.profile_device
