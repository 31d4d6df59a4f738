"""Warm-start layer: persistent, content-addressed compile cache.

Reference counterpart: the reference amortizes per-step setup with
Executor::Prepare / RunPreparedContext (reference
paddle/fluid/framework/executor.cc:337,377) and ships inference as a
pre-optimized ``__model__`` artifact — a fresh serving process never
re-runs the analysis passes. paddle_tpu's analogue of "setup" is the
XLA compile itself, and until now every process start re-traced and
re-compiled every executable. PERF.md's serving table shows that cost
landing inside the traffic window collapses the batching win from
9.7x to 1.04x; ``aot_warmup()`` only MOVES those compiles ahead of
traffic, it does not eliminate them.

This module eliminates them across processes:

* Keys are content-addressed: ``Program.fingerprint()`` (canonical
  structural hash, NOT the process-local ``_uid``) + feed specs +
  fetch names + AMP token + parallel-scope token + backend + device
  count + jax/jaxlib version strings. Any component changing (a
  Pass.apply version bump, a jaxlib upgrade, an AMP toggle) is a
  clean miss, never a stale executable.
* Values are serialized AOT executables via
  ``jax.experimental.serialize_executable``, plus the aux metadata
  (state_in/const_in/state_out names, feed/fetch lists, write-only
  carry specs, the ids of the devices it ran on) needed to rehydrate
  a compiled step with ZERO tracing on those same devices. When an
  executable refuses to serialize the entry persists lowered
  StableHLO instead — tracing is still skipped; only the backend
  compile is redone at load.
* Corrupt or stale entries are discarded with a named reason
  (``CompileCache.discards``) and the caller recompiles — a broken
  cache can slow a process down, never break it.

Placement. JAX's own persistent compilation cache and this cache
share one rule (``cache_root``): under ``$JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads the variable itself, so nothing here sets a
directory in code), else ``<checkout>/.jax_cache`` derived from this
file's path -- never the current directory, a temp dir, a pid or the
clock, because the path is part of JAX's cache key and a directory
that moves never hits. ``enable_persistent_cache()`` is the one place
entry scripts (chip_smoke.py, bench.py) turn JAX's cache on.

Gated by ``FLAGS_compile_cache={off,ro,rw}`` +
``FLAGS_compile_cache_dir``; wired through every Executor compile
path (run / run_steps / the InferenceServer aot_warmup bucket ladder)
in core/executor.py. ``FLAGS_compile_cache_max_entries`` /
``_max_bytes`` bound the on-disk size with LRU-by-mtime pruning on
write (loads refresh mtime), counted in ``prune_count`` — multi-model
churn (inference/runtime hot swap) otherwise grows the root without
bound.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import os
import pickle
import tempfile
import time
import warnings
from typing import Any, Dict, Optional, Tuple

import numpy as np

__all__ = ["CompileCache", "active_cache", "canonical_digest",
           "version_token", "cache_root", "exe_cache_root",
           "enable_persistent_cache"]

# bump when the entry layout changes: old-format entries become clean
# named-reason discards instead of unpickling hazards
_MAGIC = "ptp-exe-cache-v2"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root() -> str:
    """Directory both compile caches live under (module docstring)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_CHECKOUT, ".jax_cache")


def exe_cache_root() -> str:
    """Root of this module's executable cache:
    ``FLAGS_compile_cache_dir`` resolved against ``cache_root()``
    (an absolute flag value is a deployment setting and stands)."""
    from ..flags import FLAGS

    return os.path.join(cache_root(), FLAGS.compile_cache_dir)


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache at ``cache_root()``
    and return that directory. With ``JAX_COMPILATION_CACHE_DIR`` set
    JAX has already read it and no directory is set here."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", cache_root())
    return cache_root()


# tests force the StableHLO persistence path without uninstalling the
# serialize_executable API
_FORCE_STABLEHLO = [False]


def _canon(o):
    """json.dumps default= hook: canonicalize numpy/enum/odd values so
    digests are process-stable."""
    if isinstance(o, np.generic):
        return o.item()
    if isinstance(o, np.ndarray):
        return {"__ndarray__": o.reshape(-1).tolist(),
                "dtype": str(o.dtype), "shape": list(o.shape)}
    if isinstance(o, (set, frozenset)):
        return sorted(map(repr, o))
    value = getattr(o, "value", None)
    if value is not None and isinstance(value, (str, int)):
        return value
    return repr(o)


def canonical_digest(parts: Dict[str, Any]) -> str:
    """Stable sha256 of a JSON-canonicalized structure — the key/
    fingerprint hasher (reference analogue: the serialized
    ProgramDesc bytes that identify a `__model__` artifact, reference
    python/paddle/fluid/io.py:865 save_inference_model writes
    program.desc.serialize_to_string())."""
    blob = json.dumps(parts, sort_keys=True, default=_canon).encode()
    return hashlib.sha256(blob).hexdigest()


# computed once per process: hashing ~170 .py files (~2.5 MB) costs
# milliseconds and only runs when the cache is actually consulted
_SOURCE_TOKEN: list = []


def _source_token() -> str:
    """Content hash of the paddle_tpu package's own .py sources. The
    program fingerprint hashes op DESCS, not op KERNELS — an epsilon
    fix inside ops/ changes the compiled math without changing any
    desc, and must be a clean cache miss, not a silently-stale
    executable with the old numerics. Content-based (not mtime) so
    identical code deployed into fresh containers still warm-starts."""
    if _SOURCE_TOKEN:
        return _SOURCE_TOKEN[0]
    h = hashlib.sha256()
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    paths = []
    for dirpath, dirnames, files in os.walk(pkg_root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        paths.extend(os.path.join(dirpath, f) for f in files
                     if f.endswith(".py"))
    for p in sorted(paths):
        h.update(p[len(pkg_root):].encode())
        with open(p, "rb") as f:
            h.update(f.read())
    _SOURCE_TOKEN.append(h.hexdigest())
    return _SOURCE_TOKEN[0]


def version_token() -> Dict[str, str]:
    """Toolchain + framework version strings for the cache key
    (reference analogue: the version field baked into the serialized
    ProgramDesc, reference framework/framework.proto:188 `version`,
    checked at load): a serialized executable is an internal jaxlib
    artifact AND embeds this framework's kernel lowerings, so a bump
    of either must be a clean miss (tests spoof this to prove
    invalidation)."""
    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "paddle_tpu_src": _source_token()}


def _execution_device_ids(compiled) -> list:
    """Ids of the devices a jax.stages.Compiled runs on, in assignment
    order. Stored with the entry: deserialize_and_load defaults to
    EVERY visible device and then wants one shard per device, so a
    single-device executable would not load on a multi-device host."""
    import jax

    sh = jax.tree.leaves((compiled.input_shardings,
                          compiled.output_shardings))[0]
    mesh = getattr(sh, "mesh", None)
    devs = mesh.devices.flat if mesh is not None else sh.device_set
    return [int(d.id) for d in devs]


class _StableHLOCallable:
    """Fallback rehydration: StableHLO text -> backend compile ->
    flatten/execute/unflatten wrapper matching the traced step fn's
    calling convention. Donation annotations survive in the module's
    input_output_alias, so donated state buffers behave exactly like
    the jit path (the executor re-gathers state from the scope each
    step)."""

    def __init__(self, loaded, in_tree, out_tree, in_dtypes):
        self._loaded = loaded
        self._in_tree = in_tree
        self._out_tree = out_tree
        self._in_dtypes = in_dtypes

    def __call__(self, *args):
        import jax
        import jax.numpy as jnp

        flat = jax.tree.flatten(args)[0]
        bufs = []
        for x, want in zip(flat, self._in_dtypes):
            if not isinstance(x, jax.Array) or str(x.dtype) != want:
                x = jnp.asarray(np.asarray(x).astype(want))
            bufs.append(x)
        outs = self._loaded.execute(bufs)
        return jax.tree.unflatten(self._out_tree, list(outs))


def _compile_stablehlo(text: str, devices):
    from jax._src.lib import xla_client

    return devices[0].client.compile_and_load(
        text, devices[:1], xla_client.CompileOptions())


class CompileCache:
    """One on-disk cache root (reference analogue: the pre-optimized
    `__model__` + params directory a serving process loads instead of
    re-running analysis, reference
    inference/api/analysis_predictor.cc:78 Init — here the persisted
    artifact is the compiled executable itself). Entries are pickle
    files named by the full key digest, sharded by a 2-char prefix;
    writes are atomic (tempfile + os.replace) so concurrent processes
    can share a root."""

    _obs_seq = itertools.count(1)

    def __init__(self, root: str, mode: str):
        assert mode in ("ro", "rw"), mode
        self.root = root
        self.mode = mode
        self.hit_count = 0        # entries successfully rehydrated
        self.miss_count = 0       # no entry on disk
        self.store_count = 0      # entries written this process
        self.prune_count = 0      # entries GC'd by the size bounds
        self.discards = []        # (digest, named reason)
        # observability: counters pulled at metrics.expose() time
        # (weakref provider; instances are process-global via _CACHES,
        # one per (root, mode) — the store label keeps co-resident
        # roots from emitting duplicate series, which a scraper
        # rejects wholesale)
        from ..observability import metrics as _obs_metrics

        self._obs_id = f"disk-cache-{next(CompileCache._obs_seq)}"
        _obs_metrics.register_provider(self)

    def _metrics_samples(self):
        lab = {"mode": self.mode, "store": self._obs_id}
        s = self.stats()
        return [(f"paddle_tpu_disk_cache_{k}_total", lab, v)
                for k, v in s.items()]

    @property
    def writable(self) -> bool:
        return self.mode == "rw"

    @property
    def last_discard_reason(self) -> Optional[str]:
        return self.discards[-1][1] if self.discards else None

    def _path(self, digest: str) -> str:
        return os.path.join(self.root, digest[:2], digest + ".ptexe")

    def _discard(self, digest: str, reason: str):
        """Named-reason discard (never a crash): drop the entry from
        disk when writable so the next process recompiles cleanly."""
        self.discards.append((digest, reason))
        warnings.warn(
            f"compile_cache: discarding entry {digest[:12]}...: "
            f"{reason} (recompiling)")
        if self.writable:
            try:
                os.unlink(self._path(digest))
            except OSError:
                pass

    # --- load ---------------------------------------------------------
    def load_executable(self, digest: str):
        """Rehydrate one entry -> (callable fn, meta dict) or None.
        fn has the traced step's calling convention. Corrupt /
        undeserializable entries are discarded with a named reason."""
        path = self._path(digest)
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
        except FileNotFoundError:
            self.miss_count += 1
            return None
        except Exception as e:
            self._discard(digest, f"unreadable/corrupt entry "
                          f"({type(e).__name__}: {e})")
            return None
        if not isinstance(entry, dict) or entry.get("magic") != _MAGIC:
            self._discard(digest, "entry format mismatch (truncated "
                          "or written by an incompatible version)")
            return None
        # an executable embeds its device assignment: validate BEFORE
        # deserializing so a process without those devices (fewer
        # virtual devices, another mesh) gets a NAMED discard instead
        # of a deserialization crash deep inside jaxlib
        import jax

        by_id = {int(d.id): d for d in jax.devices()}
        want = [int(i) for i in entry.get("device_ids", [])]
        if not want or any(i not in by_id for i in want):
            mesh = (entry.get("meta") or {}).get("mesh") or {}
            self._discard(
                digest,
                f"mesh mismatch: entry compiled for device ids {want}"
                f" (mesh axes {mesh.get('axes')}); this process has "
                f"{len(by_id)} device(s) {sorted(by_id)[:8]} — "
                f"recompiling for the local devices")
            return None
        devices = [by_id[i] for i in want]
        try:
            fmt = entry["format"]
            if fmt == "aot":
                from jax.experimental.serialize_executable import \
                    deserialize_and_load

                fn = deserialize_and_load(
                    entry["payload"], entry["in_tree"],
                    entry["out_tree"], execution_devices=devices)
            elif fmt == "stablehlo":
                loaded = _compile_stablehlo(entry["payload"], devices)
                fn = _StableHLOCallable(loaded, entry["in_tree"],
                                        entry["out_tree"],
                                        entry["in_dtypes"])
            else:
                raise RuntimeError(f"unknown entry format {fmt!r}")
        except Exception as e:
            self._discard(digest, f"executable failed to rehydrate "
                          f"({type(e).__name__}: {e})")
            return None
        self.hit_count += 1
        # LRU signal for the size-bounded GC: a load refreshes the
        # entry's mtime so _prune drops cold entries, not the ones
        # serving processes still warm-start from. Deliberately NOT
        # gated on self.writable — the common fleet split is ro
        # serving processes + one rw writer doing the pruning, and an
        # ro reader that never touched mtime would look cold to the
        # writer's GC and get its hot entries evicted. mtime is cache
        # METADATA, not content; ro still never writes entries. A
        # permission failure (true read-only mount) is fine to
        # swallow: GC then degrades to FIFO for those readers.
        try:
            os.utime(path)
        except OSError:
            pass
        return fn, entry["meta"]

    # --- store --------------------------------------------------------
    def store_executable(self, digest: str, compiled, lowered,
                         out_shape, meta: Dict[str, Any]) -> bool:
        """Persist one AOT-compiled executable. `compiled` is the
        jax.stages.Compiled, `lowered` its Lowered (the StableHLO
        fallback source), `out_shape` the eval_shape output pytree
        (out_tree source when serialize() is unavailable). Failures
        are recorded, never raised — an unserializable program (e.g.
        one bridging the host via io_callback) simply stays
        process-local."""
        if not self.writable:
            return False
        import jax

        from jax.experimental.serialize_executable import serialize

        entry = {"magic": _MAGIC, "meta": meta,
                 "versions": version_token(),
                 "device_ids": _execution_device_ids(compiled)}
        try:
            if _FORCE_STABLEHLO[0]:
                raise RuntimeError("StableHLO persistence forced")
            payload, in_tree, out_tree = serialize(compiled)
            entry.update(format="aot", payload=payload,
                         in_tree=in_tree, out_tree=out_tree)
        except Exception as aot_err:
            if meta.get("mesh"):
                # the StableHLO fallback recompiles single-device at
                # load (`_compile_stablehlo`): a sharded module would
                # silently lose its mesh — stay process-local instead
                self.discards.append(
                    (digest, f"sharded executable not serializable "
                     f"(aot: {aot_err}); the StableHLO fallback is "
                     f"single-device — entry stays process-local"))
                return False
            try:
                in_avals = meta["in_avals"]
                flat, in_tree = jax.tree.flatten(in_avals)
                entry.update(
                    format="stablehlo",
                    payload=lowered.as_text(),
                    in_tree=in_tree,
                    out_tree=jax.tree.structure(out_shape),
                    in_dtypes=[str(a.dtype) for a in flat])
            except Exception as e:
                self.discards.append(
                    (digest, f"entry not serializable (aot: "
                     f"{aot_err}; stablehlo: {type(e).__name__}: "
                     f"{e})"))
                return False
        # in_avals are only needed at store time (tree/dtype
        # extraction above); keep entries lean
        entry["meta"] = {k: v for k, v in meta.items()
                         if k != "in_avals"}
        path = self._path(digest)
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                       suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    pickle.dump(entry, f)
                os.replace(tmp, path)  # atomic: readers never see a
                # half-written entry
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception as e:
            self.discards.append(
                (digest, f"entry not writable ({type(e).__name__}: "
                 f"{e})"))
            return False
        self.store_count += 1
        self._prune()
        return True

    # --- size-bounded GC ---------------------------------------------
    def _entries(self, sweep_tmps: bool = False):
        """[(path, mtime, size)] of every entry on disk (cheap: a few
        hundred stat calls at most for any sane bound).
        ``sweep_tmps`` unlinks stale ``.tmp`` debris during the SAME
        walk so the per-store GC pays one directory pass, not two."""
        now = time.time()
        out = []
        for dirpath, _dirs, files in os.walk(self.root):
            for f in files:
                p = os.path.join(dirpath, f)
                if f.endswith(".tmp"):
                    if not sweep_tmps:
                        continue
                    try:
                        if now - os.stat(p).st_mtime > \
                                self._TMP_STALE_S:
                            os.unlink(p)
                    except OSError:
                        pass
                    continue
                if not f.endswith(".ptexe"):
                    continue
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                out.append((p, st.st_mtime, st.st_size))
        return out

    def disk_usage(self) -> dict:
        entries = self._entries()
        return {"entries": len(entries),
                "bytes": int(sum(s for _, _, s in entries))}

    # a writer killed between mkstemp and os.replace leaves a
    # digest-sized .tmp that _entries() never counts; live writers
    # finish in well under a minute, so anything older is debris
    _TMP_STALE_S = 300.0

    def _prune(self):
        """LRU-by-mtime GC down to FLAGS_compile_cache_max_entries /
        _max_bytes (<= 0 = unbounded). Runs after each store; loads
        refresh mtime, so what goes is what no process warm-started
        from recently. Unlink races with concurrent writers are
        benign (missing file = already pruned)."""
        from ..flags import FLAGS

        max_entries = int(FLAGS.compile_cache_max_entries)
        max_bytes = int(FLAGS.compile_cache_max_bytes)
        if max_entries <= 0 and max_bytes <= 0:
            return  # GC off: stores stay O(1), no directory walks
        entries = self._entries(sweep_tmps=True)
        total = sum(s for _, _, s in entries)
        over_n = (len(entries) - max_entries) if max_entries > 0 else 0
        if over_n <= 0 and (max_bytes <= 0 or total <= max_bytes):
            return
        entries.sort(key=lambda e: e[1])  # oldest mtime first
        for path, _mtime, size in entries:
            if over_n <= 0 and (max_bytes <= 0 or total <= max_bytes):
                break
            try:
                os.unlink(path)
            except OSError:
                continue
            self.prune_count += 1
            over_n -= 1
            total -= size

    def stats(self) -> dict:
        return {"hits": self.hit_count, "misses": self.miss_count,
                "stores": self.store_count,
                "prunes": self.prune_count,
                "discards": len(self.discards)}


# one CompileCache per (root, mode) per process so counters aggregate
# across executors (serving clones share it the way they share the
# in-memory cache)
_CACHES: Dict[Tuple[str, str], CompileCache] = {}


def active_cache() -> Optional[CompileCache]:
    """The process's CompileCache per FLAGS, or None when off
    (reference analogue: the gflags bridge gating optional engines,
    reference python/paddle/fluid/__init__.py:129 env-flag
    allowlist)."""
    from ..flags import FLAGS

    mode = FLAGS.compile_cache
    if mode == "off":
        return None
    root = exe_cache_root()
    key = (root, mode)
    cache = _CACHES.get(key)
    if cache is None:
        cache = _CACHES[key] = CompileCache(root, mode)
    return cache
