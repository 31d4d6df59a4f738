"""Native (C++) core runtime, loaded via ctypes.

TPU-native counterpart of the reference's C++ core (reference
paddle/fluid/framework/: program_desc.h, scope.h:45, executor_gc_helper.cc;
paddle/fluid/recordio/). The compute path stays JAX/XLA; this library owns
the framework-runtime pieces the reference keeps native: the program
representation + its on-disk serialization, scope hierarchy bookkeeping,
block dataflow analysis (donation/GC planning), the RecordIO data format,
and LoD utilities. Bindings are plain ctypes (pybind11 unavailable).

The shared object is compiled on demand with g++ and cached next to the
sources, stamped with a content hash of src/*.cc,*.h (mtimes mean
nothing in a copied or checked-out tree). If compilation fails (no
toolchain), every entry point degrades to the pure-Python fallbacks
used by the callers and `build_error()` says why.
"""
from __future__ import annotations

import ctypes
import json
import os
import subprocess
import threading
from typing import List, Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src")
_LIB_PATH = os.path.join(_DIR, "_libpaddle_tpu_native.so")

_lib = None
_lib_lock = threading.Lock()
_build_error: Optional[str] = None


def _sources():
    return sorted(
        os.path.join(_SRC, f) for f in os.listdir(_SRC) if f.endswith(".cc"))


def _lib_src_hash() -> str:
    return _src_hash(_sources() + sorted(
        os.path.join(_SRC, f) for f in os.listdir(_SRC)
        if f.endswith(".h")))


def _needs_build():
    stamp = _LIB_PATH + ".srchash"
    if not (os.path.exists(_LIB_PATH) and os.path.exists(stamp)):
        return True
    with open(stamp) as f:
        return f.read().strip() != _lib_src_hash()


def _build():
    cmd = ["g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-Wall",
           "-o", _LIB_PATH] + _sources() + ["-lz"]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native build failed:\n{proc.stderr}")
    with open(_LIB_PATH + ".srchash", "w") as f:
        f.write(_lib_src_hash())


def _declare(lib):
    c = ctypes
    lib.ptp_last_error.restype = c.c_char_p
    lib.ptp_free.argtypes = [c.c_void_p]
    lib.ptp_version.restype = c.c_int

    lib.ptp_program_from_json.argtypes = [c.c_char_p]
    lib.ptp_program_from_json.restype = c.c_void_p
    lib.ptp_program_to_json.argtypes = [c.c_void_p]
    lib.ptp_program_to_json.restype = c.c_void_p  # manual decode + free
    lib.ptp_program_serialize.argtypes = [c.c_void_p,
                                          c.POINTER(c.c_size_t)]
    lib.ptp_program_serialize.restype = c.c_void_p
    lib.ptp_program_deserialize.argtypes = [c.c_char_p, c.c_size_t]
    lib.ptp_program_deserialize.restype = c.c_void_p
    lib.ptp_program_destroy.argtypes = [c.c_void_p]
    lib.ptp_program_num_blocks.argtypes = [c.c_void_p]
    lib.ptp_program_num_blocks.restype = c.c_int
    lib.ptp_program_num_ops.argtypes = [c.c_void_p, c.c_int]
    lib.ptp_program_num_ops.restype = c.c_int
    lib.ptp_program_op_type.argtypes = [c.c_void_p, c.c_int, c.c_int]
    lib.ptp_program_op_type.restype = c.c_void_p

    lib.ptp_analyze_block.argtypes = [c.c_void_p, c.c_int, c.c_char_p,
                                      c.c_char_p, c.c_char_p]
    lib.ptp_analyze_block.restype = c.c_void_p
    lib.ptp_last_use_plan.argtypes = [c.c_void_p, c.c_int, c.c_char_p,
                                      c.c_char_p]
    lib.ptp_last_use_plan.restype = c.c_void_p
    lib.ptp_dependency_waves.argtypes = [c.c_void_p, c.c_int]
    lib.ptp_dependency_waves.restype = c.c_void_p

    lib.ptp_scope_new.restype = c.c_void_p
    lib.ptp_scope_destroy.argtypes = [c.c_void_p]
    lib.ptp_scope_var.argtypes = [c.c_void_p, c.c_char_p]
    lib.ptp_scope_var.restype = c.c_int64
    lib.ptp_scope_find_var.argtypes = [c.c_void_p, c.c_char_p]
    lib.ptp_scope_find_var.restype = c.c_int64
    lib.ptp_scope_new_child.argtypes = [c.c_void_p]
    lib.ptp_scope_new_child.restype = c.c_void_p
    lib.ptp_scope_drop_kids.argtypes = [c.c_void_p]
    lib.ptp_scope_num_kids.argtypes = [c.c_void_p]
    lib.ptp_scope_num_kids.restype = c.c_int
    lib.ptp_scope_erase.argtypes = [c.c_void_p, c.c_char_p]
    lib.ptp_scope_erase.restype = c.c_int
    lib.ptp_scope_local_var_names.argtypes = [c.c_void_p]
    lib.ptp_scope_local_var_names.restype = c.c_void_p

    lib.ptp_recordio_writer_new.argtypes = [c.c_char_p, c.c_uint32,
                                            c.c_uint32, c.c_uint32]
    lib.ptp_recordio_writer_new.restype = c.c_void_p
    lib.ptp_recordio_write.argtypes = [c.c_void_p, c.c_char_p, c.c_size_t]
    lib.ptp_recordio_write.restype = c.c_int
    lib.ptp_recordio_writer_close.argtypes = [c.c_void_p]
    lib.ptp_recordio_writer_close.restype = c.c_int
    lib.ptp_recordio_writer_destroy.argtypes = [c.c_void_p]
    lib.ptp_recordio_scanner_new.argtypes = [c.c_char_p]
    lib.ptp_recordio_scanner_new.restype = c.c_void_p
    lib.ptp_recordio_next.argtypes = [c.c_void_p,
                                      c.POINTER(c.c_void_p),
                                      c.POINTER(c.c_size_t)]
    lib.ptp_recordio_next.restype = c.c_int
    lib.ptp_recordio_scanner_error.argtypes = [c.c_void_p]
    lib.ptp_recordio_scanner_error.restype = c.c_void_p
    lib.ptp_recordio_scanner_reset.argtypes = [c.c_void_p]
    lib.ptp_recordio_scanner_destroy.argtypes = [c.c_void_p]

    lib.ptp_lod_lengths_to_offsets.argtypes = [
        c.POINTER(c.c_int64), c.c_size_t, c.POINTER(c.c_size_t)]
    lib.ptp_lod_lengths_to_offsets.restype = c.c_void_p
    lib.ptp_lod_offsets_to_lengths.argtypes = [
        c.POINTER(c.c_int64), c.c_size_t, c.POINTER(c.c_size_t)]
    lib.ptp_lod_offsets_to_lengths.restype = c.c_void_p
    lib.ptp_lod_offsets_to_segment_ids.argtypes = [
        c.POINTER(c.c_int64), c.c_size_t, c.POINTER(c.c_size_t)]
    lib.ptp_lod_offsets_to_segment_ids.restype = c.c_void_p

    lib.ptp_multislot_parse.argtypes = [c.c_char_p, c.c_size_t,
                                        c.c_char_p]
    lib.ptp_multislot_parse.restype = c.c_void_p
    lib.ptp_multislot_num_slots.argtypes = [c.c_void_p]
    lib.ptp_multislot_num_slots.restype = c.c_int
    lib.ptp_multislot_slot_name.argtypes = [c.c_void_p, c.c_int]
    lib.ptp_multislot_slot_name.restype = c.c_char_p
    lib.ptp_multislot_slot_info.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_int), c.POINTER(c.c_int),
        c.POINTER(c.c_int), c.POINTER(c.c_int)]
    lib.ptp_multislot_slot_info.restype = c.c_int
    lib.ptp_multislot_ints.argtypes = [c.c_void_p, c.c_int]
    lib.ptp_multislot_ints.restype = c.POINTER(c.c_int64)
    lib.ptp_multislot_floats.argtypes = [c.c_void_p, c.c_int]
    lib.ptp_multislot_floats.restype = c.POINTER(c.c_float)
    lib.ptp_multislot_lengths.argtypes = [c.c_void_p, c.c_int]
    lib.ptp_multislot_lengths.restype = c.POINTER(c.c_int32)
    lib.ptp_multislot_destroy.argtypes = [c.c_void_p]
    return lib


def load():
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _build_error
    if _lib is not None:
        return _lib
    if _build_error is not None:
        return None
    with _lib_lock:
        if _lib is not None:
            return _lib
        try:
            if _needs_build():
                _build()
            _lib = _declare(ctypes.CDLL(_LIB_PATH))
        except Exception as exc:  # noqa: BLE001 - degrade to Python path
            _build_error = str(exc)
            return None
    return _lib


def available() -> bool:
    return load() is not None


def build_error() -> Optional[str]:
    return _build_error


def _take_string(lib, ptr) -> str:
    if not ptr:
        raise RuntimeError(lib.ptp_last_error().decode())
    try:
        return ctypes.string_at(ptr).decode()
    finally:
        lib.ptp_free(ptr)


def _names_blob(names) -> bytes:
    return "\n".join(names or []).encode()


class NativeProgram:
    """Handle to a C++ ProgramDesc (serde + dataflow analysis)."""

    def __init__(self, handle, lib):
        self._h = handle
        self._lib = lib

    # --- constructors ------------------------------------------------------
    @staticmethod
    def from_dict(d: dict) -> "NativeProgram":
        lib = load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        h = lib.ptp_program_from_json(json.dumps(d).encode())
        if not h:
            raise RuntimeError(lib.ptp_last_error().decode())
        return NativeProgram(h, lib)

    @staticmethod
    def from_bytes(data: bytes) -> "NativeProgram":
        lib = load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        h = lib.ptp_program_deserialize(data, len(data))
        if not h:
            raise RuntimeError(lib.ptp_last_error().decode())
        return NativeProgram(h, lib)

    def __del__(self):
        h, self._h = self._h, None
        if h and self._lib is not None:
            self._lib.ptp_program_destroy(h)

    # --- serde -------------------------------------------------------------
    def to_dict(self) -> dict:
        return json.loads(_take_string(self._lib,
                                       self._lib.ptp_program_to_json(self._h)))

    def to_bytes(self) -> bytes:
        size = ctypes.c_size_t()
        ptr = self._lib.ptp_program_serialize(self._h, ctypes.byref(size))
        if not ptr:
            raise RuntimeError(self._lib.ptp_last_error().decode())
        try:
            return ctypes.string_at(ptr, size.value)
        finally:
            self._lib.ptp_free(ptr)

    # --- queries -----------------------------------------------------------
    @property
    def num_blocks(self) -> int:
        return self._lib.ptp_program_num_blocks(self._h)

    def num_ops(self, block_idx=0) -> int:
        return self._lib.ptp_program_num_ops(self._h, block_idx)

    def op_type(self, block_idx, op_idx) -> str:
        return _take_string(
            self._lib, self._lib.ptp_program_op_type(self._h, block_idx,
                                                     op_idx))

    # --- analysis ----------------------------------------------------------
    def analyze_block(self, block_idx, feed_names, fetch_names,
                      skip_op_types=()):
        out = json.loads(_take_string(self._lib, self._lib.ptp_analyze_block(
            self._h, block_idx, _names_blob(feed_names),
            _names_blob(fetch_names), _names_blob(skip_op_types))))
        return out["mutated"], out["constant"], out["state_out"]

    def last_use_plan(self, block_idx, feed_names, fetch_names):
        return json.loads(_take_string(
            self._lib, self._lib.ptp_last_use_plan(
                self._h, block_idx, _names_blob(feed_names),
                _names_blob(fetch_names))))

    def dependency_waves(self, block_idx=0) -> List[int]:
        return json.loads(_take_string(
            self._lib, self._lib.ptp_dependency_waves(self._h, block_idx)))


class NativeScope:
    """Handle to a C++ Scope (name/hierarchy bookkeeping).

    Only the root owns the C++ tree; children share the root's lifetime
    (reference scope.h kids_ ownership).
    """

    def __init__(self, handle=None, lib=None, root=None):
        if handle is None:
            lib = load()
            if lib is None:
                raise RuntimeError(
                    f"native library unavailable: {_build_error}")
            handle = lib.ptp_scope_new()
        self._h = handle
        self._lib = lib
        self._root = root  # keep root alive from child handles

    def __del__(self):
        if self._root is None and getattr(self, "_h", None) \
                and self._lib is not None:
            self._lib.ptp_scope_destroy(self._h)
            self._h = None

    def var(self, name: str) -> int:
        return self._lib.ptp_scope_var(self._h, name.encode())

    def find_var(self, name: str) -> int:
        return self._lib.ptp_scope_find_var(self._h, name.encode())

    def new_scope(self) -> "NativeScope":
        child = self._lib.ptp_scope_new_child(self._h)
        return NativeScope(child, self._lib, root=self._root or self)

    def drop_kids(self):
        self._lib.ptp_scope_drop_kids(self._h)

    def num_kids(self) -> int:
        return self._lib.ptp_scope_num_kids(self._h)

    def erase(self, name: str) -> bool:
        return bool(self._lib.ptp_scope_erase(self._h, name.encode()))

    def local_var_names(self):
        return json.loads(_take_string(
            self._lib, self._lib.ptp_scope_local_var_names(self._h)))


class RecordIOWriter:
    """Chunked record file writer (reference recordio/writer.cc)."""

    def __init__(self, path, compressor=1, max_records_per_chunk=1000,
                 max_chunk_bytes=16 << 20):
        lib = load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self._h = lib.ptp_recordio_writer_new(
            str(path).encode(), compressor, max_records_per_chunk,
            max_chunk_bytes)
        if not self._h:
            raise RuntimeError(lib.ptp_last_error().decode())

    def write(self, record: bytes):
        if not self._lib.ptp_recordio_write(self._h, record, len(record)):
            raise RuntimeError("recordio write failed")

    def close(self):
        if self._h:
            ok = self._lib.ptp_recordio_writer_close(self._h)
            self._lib.ptp_recordio_writer_destroy(self._h)
            self._h = None
            if not ok:
                raise RuntimeError("recordio close failed")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.ptp_recordio_writer_close(self._h)
            self._lib.ptp_recordio_writer_destroy(self._h)
            self._h = None


class RecordIOScanner:
    """Chunk-validating record reader (reference recordio/scanner.cc)."""

    def __init__(self, path):
        lib = load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self._h = lib.ptp_recordio_scanner_new(str(path).encode())
        if not self._h:
            raise RuntimeError(lib.ptp_last_error().decode())

    def __iter__(self):
        return self

    def __next__(self) -> bytes:
        out = ctypes.c_void_p()
        size = ctypes.c_size_t()
        if not self._lib.ptp_recordio_next(self._h, ctypes.byref(out),
                                           ctypes.byref(size)):
            err = _take_string(
                self._lib, self._lib.ptp_recordio_scanner_error(self._h))
            if err:
                raise IOError(f"recordio scan error: {err}")
            raise StopIteration
        try:
            return ctypes.string_at(out.value, size.value)
        finally:
            self._lib.ptp_free(out)

    def reset(self):
        self._lib.ptp_recordio_scanner_reset(self._h)

    def close(self):
        if self._h:
            self._lib.ptp_recordio_scanner_destroy(self._h)
            self._h = None

    def __del__(self):
        self.close()


def _lod_call(fn_name, values):
    lib = load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    arr = (ctypes.c_int64 * len(values))(*values)
    out_n = ctypes.c_size_t()
    ptr = getattr(lib, fn_name)(arr, len(values), ctypes.byref(out_n))
    try:
        return list(ctypes.cast(
            ptr, ctypes.POINTER(ctypes.c_int64 * out_n.value)).contents)
    finally:
        lib.ptp_free(ptr)


def lengths_to_offsets(lengths):
    if available():
        return _lod_call("ptp_lod_lengths_to_offsets", lengths)
    out = [0]
    for n in lengths:
        out.append(out[-1] + n)
    return out


def offsets_to_lengths(offsets):
    if available():
        return _lod_call("ptp_lod_offsets_to_lengths", offsets)
    return [offsets[i + 1] - offsets[i] for i in range(len(offsets) - 1)]


def offsets_to_segment_ids(offsets):
    if available():
        return _lod_call("ptp_lod_offsets_to_segment_ids", offsets)
    out = []
    for seg in range(1, len(offsets)):
        out.extend([seg - 1] * (offsets[seg] - offsets[seg - 1]))
    return out


# ---------------------------------------------------------------------------
# C++ train demo (native/train_demo/train_demo.cc): run an exported
# train-step HLO artifact with no Python in the process — the
# reference's C++ train demo (train/demo/demo_trainer.cc) done the
# XLA-native way. Links against the XLA runtime bundled with the
# installed tensorflow wheel (libtensorflow_cc exports LocalClient).
# ---------------------------------------------------------------------------
_DEMO_BIN = os.path.join(_DIR, "_train_demo")
_demo_lock = threading.Lock()
_demo_error: Optional[str] = None


def _find_tf_root() -> Optional[str]:
    import sys

    for p in sys.path:
        cand = os.path.join(p, "tensorflow")
        if os.path.isfile(os.path.join(cand, "libtensorflow_cc.so.2")) \
                and os.path.isdir(os.path.join(cand, "include", "xla")):
            return cand
    return None


def build_train_demo() -> str:
    """Compile (once) and return the path of the train_demo binary.
    Raises RuntimeError when the toolchain or the XLA runtime is
    unavailable."""
    global _demo_error
    with _demo_lock:
        src = os.path.join(_DIR, "train_demo", "train_demo.cc")
        deps = [src, os.path.join(_SRC, "json.cc"),
                os.path.join(_SRC, "json.h")]
        if os.path.exists(_DEMO_BIN) and all(
                os.path.getmtime(_DEMO_BIN) >= os.path.getmtime(d)
                for d in deps):
            return _DEMO_BIN
        if _demo_error is not None:
            raise RuntimeError(_demo_error)
        tf = _find_tf_root()
        if tf is None:
            _demo_error = ("train_demo: no bundled XLA runtime "
                           "(tensorflow wheel with libtensorflow_cc) "
                           "found on sys.path")
            raise RuntimeError(_demo_error)
        inc = os.path.join(tf, "include")
        cmd = ["g++", "-std=c++17", "-O1", src,
               os.path.join(_SRC, "json.cc"),
               "-I" + inc,
               "-I" + os.path.join(inc, "external", "highwayhash"),
               "-I" + os.path.join(inc, "external", "farmhash_archive",
                                   "src"),
               os.path.join(tf, "libtensorflow_cc.so.2"),
               os.path.join(tf, "libtensorflow_framework.so.2"),
               "-Wl,-rpath," + tf,
               "-o", _DEMO_BIN]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            _demo_error = ("train_demo build failed: "
                           + proc.stderr[-2000:])
            raise RuntimeError(_demo_error)
        return _DEMO_BIN


def run_train_demo(artifact_dir: str, steps: int,
                   timeout: int = 600) -> List[dict]:
    """Run the C++ driver over an `export_train_hlo` artifact for
    `steps` steps; returns the per-step fetch dicts it printed. Final
    state lands next to the artifact's data files as *.bin.final."""
    binary = build_train_demo()
    proc = subprocess.run(
        [binary, str(artifact_dir), str(int(steps))],
        capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"train_demo failed (exit {proc.returncode}): "
            f"{proc.stderr[-2000:]}")
    out = []
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


# ---------------------------------------------------------------------------
# C++ XLA-computation builder (native/xla_train/xla_train.cc): the
# train-step XLA program is BUILT in C++ by per-op registry kernels
# over the native ProgramDesc (reference op_registry.h:197-270
# REGISTER_OPERATOR analogue), then compiled and driven with no Python
# in the process. Python's trace path is the numerical oracle.
# ---------------------------------------------------------------------------
_XLA_TRAIN_BIN = os.path.join(_DIR, "_xla_train")
_xla_train_lock = threading.Lock()
# (source-hash, tf-root) -> error message: a failure is retried when
# either the sources change or a different toolchain appears, instead
# of latching the first error for the process lifetime
_xla_train_error: dict = {}


def _xla_train_deps():
    return [os.path.join(_DIR, "xla_train", "xla_train.cc"),
            os.path.join(_SRC, "json.cc"),
            os.path.join(_SRC, "json.h"),
            os.path.join(_SRC, "program.cc"),
            os.path.join(_SRC, "program.h")]


def _src_hash(paths) -> str:
    """Content hash of the native sources. Freshness must NOT use
    mtimes: git checkouts do not preserve them, so a stale (or
    foreign) binary could shadow newer sources."""
    import hashlib

    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def build_xla_train() -> str:
    """Compile (once per source state) and return the binary path."""
    with _xla_train_lock:
        deps = _xla_train_deps()
        tf = _find_tf_root()
        # stamp = sources hash + toolchain root: a binary linked
        # against a removed/replaced tensorflow wheel must rebuild,
        # not be served stale
        want = _src_hash(deps) + ":" + str(tf)
        stamp = _XLA_TRAIN_BIN + ".srchash"
        if os.path.exists(_XLA_TRAIN_BIN) and os.path.exists(stamp):
            with open(stamp) as f:
                if f.read().strip() == want:
                    return _XLA_TRAIN_BIN
        key = (want, tf)
        if key in _xla_train_error:
            raise RuntimeError(_xla_train_error[key])
        if tf is None:
            _xla_train_error[key] = (
                "xla_train: no bundled XLA runtime (tensorflow wheel "
                "with libtensorflow_cc) found on sys.path")
            raise RuntimeError(_xla_train_error[key])
        inc = os.path.join(tf, "include")
        cmd = ["g++", "-std=c++17", "-O1", deps[0],
               os.path.join(_SRC, "json.cc"),
               os.path.join(_SRC, "program.cc"),
               "-I" + inc,
               "-I" + os.path.join(inc, "external", "highwayhash"),
               "-I" + os.path.join(inc, "external", "farmhash_archive",
                                   "src"),
               os.path.join(tf, "libtensorflow_cc.so.2"),
               os.path.join(tf, "libtensorflow_framework.so.2"),
               "-Wl,-rpath," + tf,
               "-o", _XLA_TRAIN_BIN]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            _xla_train_error[key] = ("xla_train build failed: "
                                     + proc.stderr[-2000:])
            raise RuntimeError(_xla_train_error[key])
        with open(stamp, "w") as f:
            f.write(want)
        return _XLA_TRAIN_BIN


def run_xla_train(artifact_dir: str, steps: int,
                  timeout: int = 600) -> List[dict]:
    """Run the native-builder driver over an `export_train_program`
    artifact for `steps` steps; returns the per-step fetch dicts.
    Final state lands next to the data files as *.bin.final."""
    binary = build_xla_train()
    proc = subprocess.run(
        [binary, str(artifact_dir), str(int(steps))],
        capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"xla_train failed (exit {proc.returncode}): "
            f"{proc.stderr[-2000:]}")
    out = []
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            out.append(json.loads(line))
    return out
