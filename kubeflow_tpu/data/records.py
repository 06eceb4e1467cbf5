"""Native record IO: the C++ input pipeline's Python surface.

Binds ``native/kftdata.cpp`` (built on demand with g++ into ``build/native``)
via ctypes — no pybind11 in this image (SURVEY.md §0). The native library
owns the hot path: record reads, seeded shuffle, batch assembly, and a
bounded prefetch queue run in a C++ producer thread; Python receives one
contiguous buffer per batch and reshapes it zero-copy into numpy arrays
for ``jax.device_put`` / ``make_array_from_process_local_data``.

A record is a fixed-size pack of the example's fields (static shapes are
the XLA-friendly contract). ``RecordSpec`` maps field names/dtypes/shapes
to byte offsets; ``write_records`` / ``RecordLoader`` round-trip it.
``PyRecordLoader`` is the dependency-free fallback with identical
semantics for hosts without a toolchain.
"""

from __future__ import annotations

import ctypes
import dataclasses
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Iterator, Mapping, Sequence

import numpy as np

_REPO = Path(__file__).resolve().parents[2]
_SRC = _REPO / "native" / "kftdata.cpp"
_BUILD_DIR = _REPO / "build" / "native"
_MAGIC = 0x4B465452
_HEADER = np.dtype(
    [("magic", "<u4"), ("record_bytes", "<u4"), ("count", "<u8")]
)

_build_lock = threading.Lock()
_lib: ctypes.CDLL | None = None


class NativeBuildError(RuntimeError):
    pass


def ensure_built() -> Path:
    """Compile libkftdata.so under the checkout's ignored ``build/``
    directory, named by the source's content hash — a library built from
    another checkout or an older source is never loaded. Compiles to a
    per-pid temp name and publishes with os.replace so concurrent
    processes never dlopen a half-written .so."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    out = _BUILD_DIR / f"libkftdata-{digest}.so"
    if out.exists():
        return out
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    tmp = out.with_suffix(f".so.tmp-{os.getpid()}")
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        str(_SRC), "-o", str(tmp),
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        tmp.unlink(missing_ok=True)
        raise NativeBuildError(
            f"g++ failed ({proc.returncode}):\n{proc.stderr[-2000:]}"
        )
    os.replace(tmp, out)
    return out


def load_library() -> ctypes.CDLL:
    global _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(str(ensure_built()))
        lib.kft_loader_open.restype = ctypes.c_void_p
        lib.kft_loader_open.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_uint32,
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.c_int, ctypes.c_uint32, ctypes.c_uint32, ctypes.c_int32,
        ]
        lib.kft_loader_next.restype = ctypes.c_int
        lib.kft_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.kft_loader_close.argtypes = [ctypes.c_void_p]
        lib.kft_write_records.restype = ctypes.c_int64
        lib.kft_write_records.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_uint32, ctypes.c_uint64,
        ]
        lib.kft_last_error.restype = ctypes.c_char_p
        _lib = lib
        return lib


def native_available() -> bool:
    try:
        load_library()
        return True
    except (NativeBuildError, OSError):
        return False


# --------------------------------------------------------------------- #
# record schema
# --------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    dtype: str
    shape: tuple[int, ...]

    @property
    def nbytes(self) -> int:
        return int(np.dtype(self.dtype).itemsize * int(np.prod(self.shape or (1,))))


@dataclasses.dataclass(frozen=True)
class RecordSpec:
    """Fixed-size record layout: fields packed back to back."""

    fields: tuple[Field, ...]

    @classmethod
    def of(cls, **fields: tuple[str, tuple[int, ...]]) -> "RecordSpec":
        return cls(
            tuple(Field(k, dt, tuple(shape)) for k, (dt, shape) in fields.items())
        )

    @property
    def record_bytes(self) -> int:
        return sum(f.nbytes for f in self.fields)

    def pack(self, batch: Mapping[str, np.ndarray]) -> np.ndarray:
        """Dict of [n, *shape] arrays → [n, record_bytes] u8."""
        n = len(next(iter(batch.values())))
        out = np.empty((n, self.record_bytes), dtype=np.uint8)
        off = 0
        for f in self.fields:
            arr = np.ascontiguousarray(batch[f.name], dtype=f.dtype)
            if arr.shape != (n, *f.shape):
                raise ValueError(
                    f"field {f.name!r}: expected {(n, *f.shape)}, got {arr.shape}"
                )
            out[:, off : off + f.nbytes] = arr.reshape(n, -1).view(np.uint8)
            off += f.nbytes
        return out

    def unpack(self, buf: np.ndarray, n: int) -> dict[str, np.ndarray]:
        """[batch, record_bytes] u8 → dict of [n, *shape] arrays (views)."""
        out = {}
        off = 0
        for f in self.fields:
            flat = buf[:n, off : off + f.nbytes]
            out[f.name] = (
                np.ascontiguousarray(flat).view(f.dtype).reshape(n, *f.shape)
            )
            off += f.nbytes
        return out


def write_records(
    path: str | os.PathLike,
    spec: RecordSpec,
    batch: Mapping[str, np.ndarray],
) -> int:
    """Write one KFTR file; returns the record count."""
    packed = spec.pack(batch)
    n = len(packed)
    lib = load_library()
    buf = np.ascontiguousarray(packed)
    written = lib.kft_write_records(
        str(path).encode(),
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        spec.record_bytes,
        n,
    )
    if written < 0:
        raise OSError(lib.kft_last_error().decode())
    return int(written)


def write_records_py(
    path: str | os.PathLike,
    spec: RecordSpec,
    batch: Mapping[str, np.ndarray],
) -> int:
    """Pure-Python writer (same format)."""
    packed = spec.pack(batch)
    header = np.zeros(1, dtype=_HEADER)
    header["magic"] = _MAGIC
    header["record_bytes"] = spec.record_bytes
    header["count"] = len(packed)
    with open(path, "wb") as f:
        f.write(header.tobytes())
        f.write(packed.tobytes())
    return len(packed)


# --------------------------------------------------------------------- #
# loaders
# --------------------------------------------------------------------- #


class RecordLoader:
    """Iterate KFTR files as dict-of-ndarray batches via the C++ pipeline.

    ``shard_index/shard_count`` deterministically partition records across
    data-parallel processes; ``epochs=-1`` loops forever (training);
    ``shuffle_records=0/1`` disables shuffling (eval).
    """

    def __init__(
        self,
        files: Sequence[str | os.PathLike],
        spec: RecordSpec,
        *,
        batch_size: int,
        shuffle_records: int = 0,
        seed: int = 0,
        prefetch_batches: int = 2,
        drop_remainder: bool = True,
        shard_index: int = 0,
        shard_count: int = 1,
        epochs: int = 1,
    ):
        self.spec = spec
        self.batch_size = batch_size
        self._lib = load_library()
        arr = (ctypes.c_char_p * len(files))(
            *[str(f).encode() for f in files]
        )
        self._handle = self._lib.kft_loader_open(
            arr, len(files), spec.record_bytes, batch_size,
            shuffle_records, seed, 1, prefetch_batches,
            int(drop_remainder), shard_index, shard_count, epochs,
        )
        if not self._handle:
            raise OSError(self._lib.kft_last_error().decode())
        self._buf = np.empty(
            (batch_size, spec.record_bytes), dtype=np.uint8
        )

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        return self

    def __next__(self) -> dict[str, np.ndarray]:
        if self._handle is None:
            raise StopIteration
        n = ctypes.c_uint64(0)
        ok = self._lib.kft_loader_next(
            self._handle,
            self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.byref(n),
        )
        if not ok:
            err = self._lib.kft_last_error().decode()
            self.close()
            if err:
                raise OSError(err)
            raise StopIteration
        # copy out of the reused fill buffer: unpack() returns views, and a
        # consumer holding batch N across next() must not see batch N+1
        return self.spec.unpack(self._buf.copy(), int(n.value))

    def skip(self, n: int) -> "RecordLoader":
        """Consume ``n`` batches without surfacing them (no unpack, no copy
        out of the fill buffer) — the ``start_step → iterator`` resume
        contract for record streams: a factory built as
        ``lambda s: make_loader(...).skip(s)`` replays the stream to the
        restored step so batches buffered in a prefetcher at shutdown are
        regenerated, never lost or double-consumed."""
        m = ctypes.c_uint64(0)
        for _ in range(n):
            ok = self._handle is not None and self._lib.kft_loader_next(
                self._handle,
                self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.byref(m),
            )
            if not ok:
                err = self._lib.kft_last_error().decode() if self._handle else ""
                self.close()
                if err:
                    raise OSError(err)
                break  # stream shorter than the skip: iteration will stop
        return self

    def close(self) -> None:
        if self._handle is not None:
            self._lib.kft_loader_close(self._handle)
            self._handle = None

    def __enter__(self) -> "RecordLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # pragma: no cover - GC safety net
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


class PyRecordLoader:
    """Toolchain-free fallback with the same iteration contract (no
    background prefetch; fine for tests and small evals)."""

    def __init__(
        self,
        files: Sequence[str | os.PathLike],
        spec: RecordSpec,
        *,
        batch_size: int,
        shuffle_records: int = 0,
        seed: int = 0,
        drop_remainder: bool = True,
        shard_index: int = 0,
        shard_count: int = 1,
        epochs: int = 1,
        **_ignored,
    ):
        self.files = [str(f) for f in files]
        self.spec = spec
        self.batch_size = batch_size
        self.shuffle = shuffle_records
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.shard_index = shard_index
        self.shard_count = max(1, shard_count)
        self.epochs = epochs
        self._gen = self._iterate()

    def _epoch_records(self) -> Iterator[np.ndarray]:
        """One epoch's worth of this shard's records, in file order."""
        index = 0
        for path in self.files:
            raw = np.fromfile(path, dtype=np.uint8)
            header = raw[: _HEADER.itemsize].view(_HEADER)[0]
            rb = int(header["record_bytes"])
            if header["magic"] != _MAGIC or rb != self.spec.record_bytes:
                # same contract as the native loader: a record-size
                # mismatch must fail fast, never parse at wrong offsets
                raise OSError(f"bad header in {path}")
            body = raw[_HEADER.itemsize :].reshape(-1, rb)
            for rec in body:
                if index % self.shard_count == self.shard_index:
                    yield rec
                index += 1

    def _iterate(self) -> Iterator[dict[str, np.ndarray]]:
        rng = np.random.RandomState(self.seed % (2**31 - 1))
        pool: list[np.ndarray] = []
        pending: list[np.ndarray] = []

        def emit(rec):
            pending.append(rec)
            if len(pending) == self.batch_size:
                buf = np.stack(pending)
                pending.clear()
                return buf
            return None

        def drain(keep: int):
            # Fisher-Yates-style random draws, same shape as the native
            # loader's drain_pool (kftdata.cpp): pick, swap last into the
            # hole, emit.
            while len(pool) > keep:
                pick = rng.randint(len(pool))
                pool[pick], pool[-1] = pool[-1], pool[pick]
                out = emit(pool.pop())
                if out is not None:
                    yield self.spec.unpack(out, len(out))

        # Epochs are explicit so the pool FULLY drains at every epoch
        # boundary — the native loader calls drain_pool(true) per epoch, so
        # records never mix across epochs regardless of which loader
        # make_loader picks. The partial batch (`pending`) DOES persist
        # across epochs in both loaders.
        epoch = 0
        while self.epochs < 0 or epoch < self.epochs:
            for rec in self._epoch_records():
                if self.shuffle > 1:
                    pool.append(rec.copy())
                    if len(pool) >= self.shuffle:
                        yield from drain(self.shuffle // 2)
                else:
                    out = emit(rec.copy())
                    if out is not None:
                        yield self.spec.unpack(out, len(out))
            yield from drain(0)
            epoch += 1
        if pending and not self.drop_remainder:
            buf = np.stack(pending)
            yield self.spec.unpack(buf, len(buf))

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._gen)

    def skip(self, n: int) -> "PyRecordLoader":
        """Same resume contract as :meth:`RecordLoader.skip`."""
        for _ in range(n):
            try:
                next(self._gen)
            except StopIteration:
                break
        return self

    def close(self) -> None:
        pass


def make_loader(*args, **kwargs):
    """RecordLoader when the native library builds, else PyRecordLoader."""
    if native_available():
        return RecordLoader(*args, **kwargs)
    return PyRecordLoader(*args, **kwargs)
