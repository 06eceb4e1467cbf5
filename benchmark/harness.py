"""What every runner shares: the device gate, the set-up clock, compile
accounting, the profiler window and the device report."""

from __future__ import annotations

import contextlib
import shutil
import threading
import time
from typing import Any

from benchmark import trace_reduce
from benchmark.manifest import ROOT

#: run-time output (profiler traces), inside the checkout and git-ignored
OUT_DIR = ROOT / ".bench_out"


class NoAccelerator(SystemExit):
    """The cell's chips are not there. Exit code 3, no result line: a
    measurement path that finds no chip fails, it never falls back."""

    def __init__(self, message: str):
        super().__init__(3)
        self.message = message


def require_tpu(chips: int) -> dict[str, Any]:
    import jax

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if found["platform"] != "tpu" or found["count"] != chips:
        raise NoAccelerator(
            f"this cell needs {chips} TPU chip(s); JAX found platform "
            f"{found['platform']!r} ({found['kind']}) x{found['count']}"
        )
    return found


def live_bytes() -> int:
    """Bytes held at this moment on the fullest chip. The TPU allocator
    counts buffers (``bytes_in_use``: parameters, state, caches) apart from
    what running programs have reserved for their temporaries
    (``bytes_reserved``); their sum at one moment is memory really held.
    The runners sample it while the window runs."""
    import jax

    return max(
        int(s.get("bytes_in_use", 0)) + int(s.get("bytes_reserved", 0))
        for s in (d.memory_stats() or {} for d in jax.devices())
    )


def memory_peak_bytes(sampled: list[int]) -> int:
    """Peak bytes on the fullest chip: the most ``live_bytes`` saw in the
    window, and never less than the allocator's own peak of buffers. (The
    allocator's two peaks, ``peak_bytes_in_use + peak_bytes_reserved``, need
    not fall at the same moment: their sum is an upper bound, kept in the
    notes line under ``memory_stats``.)"""
    import jax

    buffers = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()
    )
    return max([buffers, *sampled])


def memory_stats() -> dict[str, int]:
    """The allocator's whole report for chip 0, for the notes line."""
    import jax

    return dict(jax.devices()[0].memory_stats() or {})


def compile_stats() -> dict[str, float]:
    """Programs built, seconds building them, persistent-cache hits — the
    program's own counters over JAX's compile events."""
    from kubeflow_tpu.core import compcache

    return compcache.compile_stats()


class TraceWindow:
    """Profile ``seconds`` of the measured window, starting ``after``
    seconds into it, from a thread of its own; ``reduction()`` afterwards.
    The traced part is bracketed by the ``bench.trace_window`` annotation,
    which the reduction takes as its window."""

    def __init__(self, name: str, *, after: float, seconds: float):
        self.logdir = OUT_DIR / "trace" / name
        self.after, self.seconds = after, seconds
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    def start(self) -> None:
        shutil.rmtree(self.logdir, ignore_errors=True)
        self.logdir.mkdir(parents=True, exist_ok=True)
        self._thread = threading.Thread(target=self._run, name="bench-profiler")
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.after)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # host TraceMe only: far cheaper
            jax.profiler.start_trace(str(self.logdir), profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
                    time.sleep(self.seconds)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — re-raised by reduction()
            self._error = e

    def reduction(self) -> trace_reduce.Reduction:
        self._thread.join()
        if self._error is not None:
            raise self._error
        path = trace_reduce.newest_xplane(str(self.logdir))
        return trace_reduce.reduce(trace_reduce.load(path))


@contextlib.contextmanager
def annotate(name: str):
    """A host span in the profiler's own trace (no-op when none runs)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield
