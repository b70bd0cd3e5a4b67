"""Compile manager (layer L4 — compilation control).

PR 1's telemetry *detects* recompile storms (the watchdog samples the jitted
step's executable cache and fingerprints the offending batch); nothing in the
repo *prevented* them. On TPU every distinct batch shape pays a full XLA
trace + lower + compile — tens of seconds each at real-model scale — so a
stream of ragged batches, a ragged final batch each epoch, or a cold restart
are the dominant silent perf killers. :class:`CompileManager` makes the
compile boundary a managed artifact, three ways:

1. **Shape bucketing** — a bucket policy (``pow2`` ladder, explicit
   ``fixed`` ladders, or ``auto`` from previously observed shapes) pads the
   batch and sequence dims at the device boundary
   (:meth:`CompileManager.bucket_pad`, called by
   ``BaseDataLoader._device_put_batch``), so a stream of ragged batches
   compiles at most ``len(buckets)`` executables instead of one per shape.

2. **AOT warmup** — every distinct post-bucketing ``(shape, dtype)``
   signature is recorded to a per-project ``shapes_manifest.jsonl`` (fed both
   by the manager's own step observation and by the telemetry watchdog's
   digests). On the next run, ``prepare_train_step`` warms every manifest
   entry **before step 0**. Two modes:

   - ``"execute"`` (default): run the real jitted step on a *copy* of the
     train state with zero-filled dummy batches. This is the only mode that
     populates jit's dispatch cache — measured on jax 0.4.x,
     ``lower().compile()`` leaves ``_cache_size()`` at 0, so an AOT-only
     warmup still pays trace+dispatch insertion (and the recompile-watchdog
     count) on the first real batch. Each signature is executed
     ``warmup_calls`` times (default 2) to also absorb the second-call
     recompile on a mesh (the state returns in GSPMD's shardings once).
   - ``"aot"``: classic ``jit(...).lower(abstract).compile()``. Cheaper (no
     state copy, no step executed) and it primes the *persistent* cache, but
     the first real call per shape still re-traces.

3. **Persistent-cache control** — the bare ``JitConfig.persistent_cache_dir``
   passthrough becomes a managed cache: the dir is validated/created at
   ``Accelerator`` init (``warning_once`` instead of handing a bad path to
   ``jax.config``), hit/miss and size stats surface in the telemetry
   summary, and ``close()`` prunes by mtime-LRU to a byte budget.

Enabled by passing :class:`~accelerate_tpu.utils.CompileKwargs` to
``Accelerator(kwargs_handlers=[...])``. Off by default: without the handler
``accelerator.compile_manager`` is ``None`` and every hook site is a single
``None`` check — behavior is byte-identical to the unmanaged path.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .logging import get_logger

logger = get_logger(__name__)

MANIFEST_NAME = "shapes_manifest.jsonl"
CACHE_SUBDIR = "compile_cache"


# ---------------------------------------------------------------------------
# Bucket-policy math (pure functions — unit-tested directly)
# ---------------------------------------------------------------------------


def pow2_bucket(n: int, min_bucket: int = 1, max_bucket: Optional[int] = None) -> Optional[int]:
    """Smallest power of two >= ``n`` (floored at ``min_bucket``), or ``None``
    when it would exceed ``max_bucket`` — the oversize fall-through."""
    if n <= 0:
        return min_bucket
    b = max(min_bucket, 1 << (int(n) - 1).bit_length())
    if max_bucket is not None and b > max_bucket:
        return None
    return b


def ladder_bucket(n: int, ladder) -> Optional[int]:
    """Smallest ladder rung >= ``n``, or ``None`` when ``n`` overshoots the
    ladder."""
    for b in sorted(int(x) for x in ladder):
        if n <= b:
            return b
    return None


# ---------------------------------------------------------------------------
# Batch spec (de)serialization — what the manifest stores per signature
# ---------------------------------------------------------------------------


def tree_to_spec(tree) -> Any:
    """JSON-serializable skeleton of a batch pytree: containers survive as
    dict/list/tuple, array leaves become ``{"shape", "dtype"}``. Covers every
    batch structure the loaders emit (dicts, tuples, bare arrays)."""
    if isinstance(tree, dict):
        return {"kind": "dict", "items": {str(k): tree_to_spec(v) for k, v in tree.items()}}
    if isinstance(tree, (list, tuple)):
        return {
            "kind": "tuple" if isinstance(tree, tuple) else "list",
            "items": [tree_to_spec(v) for v in tree],
        }
    shape = getattr(tree, "shape", None)
    dtype = getattr(tree, "dtype", None)
    if shape is None or dtype is None:
        return {"kind": "opaque", "type": type(tree).__name__}
    return {"kind": "array", "shape": [int(d) for d in shape], "dtype": str(dtype)}


def spec_map_leaves(spec, fn):
    """Rebuild a pytree from a spec, calling ``fn(shape, dtype)`` per array
    leaf. Raises ``ValueError`` on opaque leaves (unwarmable signature)."""
    kind = spec.get("kind")
    if kind == "dict":
        return {k: spec_map_leaves(v, fn) for k, v in spec["items"].items()}
    if kind in ("list", "tuple"):
        items = [spec_map_leaves(v, fn) for v in spec["items"]]
        return tuple(items) if kind == "tuple" else items
    if kind == "array":
        return fn(tuple(spec["shape"]), spec["dtype"])
    raise ValueError(f"unwarmable manifest leaf of kind {kind!r}")


def spec_array_dims(spec, out: Optional[dict] = None) -> dict:
    """Collect observed dim sizes from a spec: ``{"batch": set, "seq": set}``
    — the raw material for the ``auto`` bucket ladder."""
    if out is None:
        out = {"batch": set(), "seq": set()}
    kind = spec.get("kind")
    if kind == "dict":
        for v in spec["items"].values():
            spec_array_dims(v, out)
    elif kind in ("list", "tuple"):
        for v in spec["items"]:
            spec_array_dims(v, out)
    elif kind == "array":
        shape = spec["shape"]
        if len(shape) >= 1:
            out["batch"].add(int(shape[0]))
        if len(shape) >= 2:
            out["seq"].add(int(shape[1]))
    return out


def batch_digest(batch) -> str:
    """Shape/dtype fingerprint — same digest the telemetry watchdog records,
    so manifest entries and watchdog warnings cross-reference."""
    from .telemetry import _batch_digest

    return _batch_digest(batch)


# ---------------------------------------------------------------------------
# Shapes manifest — the cross-run memory of observed signatures
# ---------------------------------------------------------------------------


class ShapesManifest:
    """Append-only JSONL of observed batch signatures, one line per NEW
    signature: ``{"digest", "spec", "time"}``. Crash-safe like the telemetry
    report (each line is durable on its newline); duplicate digests are
    dropped at record time, so replaying a manifest is idempotent."""

    def __init__(self, path: str):
        self.path = path
        self._digests: set = set()
        self._entries: list[dict] = []
        self._load()

    def _load(self):
        if not os.path.exists(self.path):
            return
        try:
            with open(self.path) as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        entry = json.loads(line)
                    except ValueError:
                        continue  # torn tail line from a preempted run
                    digest = entry.get("digest")
                    if digest and digest not in self._digests and "spec" in entry:
                        self._digests.add(digest)
                        self._entries.append(entry)
        except OSError as e:
            logger.warning("compile_manager: could not read shapes manifest %s: %s", self.path, e)

    @property
    def entries(self) -> list[dict]:
        return list(self._entries)

    def __len__(self):
        return len(self._entries)

    def __contains__(self, digest: str) -> bool:
        return digest in self._digests

    def record(self, digest: str, spec) -> bool:
        """Append one signature; returns True when it was new."""
        if digest in self._digests:
            return False
        entry = {"digest": digest, "spec": spec, "time": time.time()}
        self._digests.add(digest)
        self._entries.append(entry)
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            with open(self.path, "a", buffering=1) as fh:
                fh.write(json.dumps(entry) + "\n")
        except OSError as e:
            logger.warning_once(
                "compile_manager: cannot append to shapes manifest %s (%s) — "
                "warmup will not cover this run's shapes on restart.", self.path, str(e)
            )
        return True


def manifest_path_for(accelerator) -> Optional[str]:
    """Default manifest location: ``<project_dir>/compile_cache/shapes_manifest.jsonl``."""
    if accelerator.project_dir is None:
        return None
    return os.path.join(accelerator.project_dir, CACHE_SUBDIR, MANIFEST_NAME)


def record_watchdog_signature(accelerator, batch, digest: str) -> None:
    """Telemetry-watchdog → manifest bridge: called on every NEW step-batch
    digest the watchdog sees. Routes through the compile manager when one
    exists (shared dedup set); otherwise writes a standalone manifest under
    the project dir so a *future* run with the manager enabled can warm from
    a telemetry-only run's observations."""
    cm = getattr(accelerator, "compile_manager", None)
    if cm is not None:
        cm.record_digest(digest, batch)
        return
    manifest = getattr(accelerator, "_shapes_manifest", None)
    if manifest is None:
        path = manifest_path_for(accelerator)
        if path is None:
            return
        manifest = ShapesManifest(path)
        accelerator._shapes_manifest = manifest
    manifest.record(digest, tree_to_spec(batch))


# ---------------------------------------------------------------------------
# Persistent executable cache — validation, stats, LRU pruning
# ---------------------------------------------------------------------------


def place_compile_cache() -> str:
    """Place JAX's persistent compilation cache for an entry script
    (``chip_smoke.py``, ``chipbench.run``): call it first thing.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
    sets another directory. Unset: ``<checkout>/.jax_cache`` — a fixed path,
    never a temp dir, because a cache that moves between runs never hits.
    Returns the directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def configure_persistent_cache(jit_config) -> Optional[str]:
    """Validate ``JitConfig.persistent_cache_dir`` at Accelerator init:
    create it, check writability (``warning_once`` instead of silently
    handing a bad path to ``jax.config``), and wire the min-compile-time
    knob. Returns the directory in effect, or ``None`` when unusable.

    ``JAX_COMPILATION_CACHE_DIR`` outranks the config: whoever runs the
    process places the cache, so with it set this returns that directory
    and sets nothing."""
    path = jit_config.persistent_cache_dir
    if not path:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.abspath(os.path.expanduser(path))
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        logger.warning_once(
            "JitConfig.persistent_cache_dir=%s cannot be created (%s) — "
            "persistent compilation cache DISABLED for this run.", path, str(e)
        )
        return None
    if not os.access(path, os.W_OK):
        logger.warning_once(
            "JitConfig.persistent_cache_dir=%s is not writable — persistent "
            "compilation cache DISABLED for this run.", path
        )
        return None
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(jit_config.persistent_cache_min_compile_time_secs),
    )
    return path


class ManagedPersistentCache:
    """Size/hit accounting and LRU pruning over the XLA persistent
    compilation cache directory. JAX gives no hit/miss API, so misses are
    measured as files that appeared since this run started; hits are compile
    events the run observed beyond those (an estimate, labeled as such)."""

    def __init__(self, cache_dir: str, budget_bytes: Optional[int] = None):
        self.dir = cache_dir
        self.budget_bytes = budget_bytes
        self._baseline = set(self._files())

    def _files(self) -> dict:
        out = {}
        try:
            for root, _dirs, files in os.walk(self.dir):
                for name in files:
                    p = os.path.join(root, name)
                    try:
                        st = os.stat(p)
                    except OSError:
                        continue
                    out[p] = (st.st_size, st.st_mtime)
        except OSError:
            pass
        return out

    def stats(self, compile_events: int = 0) -> dict:
        files = self._files()
        new = [p for p in files if p not in self._baseline]
        misses = len(new)
        return {
            "dir": self.dir,
            "files": len(files),
            "bytes": int(sum(s for s, _ in files.values())),
            "misses": misses,  # executables compiled fresh this run
            "estimated_hits": max(0, int(compile_events) - misses),
        }

    def prune(self) -> dict:
        """Remove oldest-mtime entries until the cache fits the byte budget.
        Never removes files created by THIS run (they are the hot set)."""
        if not self.budget_bytes:
            return {"removed_files": 0, "removed_bytes": 0}
        files = self._files()
        total = sum(s for s, _ in files.values())
        removed_files = removed_bytes = 0
        if total <= self.budget_bytes:
            return {"removed_files": 0, "removed_bytes": 0}
        # Oldest first; this run's entries are excluded from eviction.
        evictable = sorted(
            ((p, sz, mt) for p, (sz, mt) in files.items() if p in self._baseline),
            key=lambda x: x[2],
        )
        for p, sz, _mt in evictable:
            if total <= self.budget_bytes:
                break
            try:
                os.remove(p)
            except OSError:
                continue
            total -= sz
            removed_files += 1
            removed_bytes += sz
        if removed_files:
            logger.info(
                "compile_manager: pruned %d cache entries (%d bytes) from %s "
                "to meet the %d-byte budget.",
                removed_files, removed_bytes, self.dir, self.budget_bytes,
            )
        return {"removed_files": removed_files, "removed_bytes": removed_bytes}


# ---------------------------------------------------------------------------
# The manager
# ---------------------------------------------------------------------------


def _cache_size(fn) -> Optional[int]:
    size_fn = getattr(fn, "_cache_size", None)
    if callable(size_fn):
        try:
            return int(size_fn())
        except Exception:
            return None
    return None


class CompileManager:
    """Owned by :class:`~accelerate_tpu.Accelerator` when a
    :class:`~accelerate_tpu.utils.CompileKwargs` handler is passed. One
    instance per Accelerator; all hook sites are ``None`` checks when off."""

    def __init__(self, accelerator, handler):
        self.accelerator = accelerator
        self.handler = handler
        path = handler.manifest_path or manifest_path_for(accelerator)
        self.manifest = ShapesManifest(path) if path else None
        self._seen: set = set(self.manifest._digests) if self.manifest else set()
        self._steps: list[dict] = []
        self._auto_ladders: Optional[dict] = None
        self._plan = None  # resolved ParallelPlan, via note_plan
        self.pad_events = 0
        self.oversize_events = 0
        self.warmup_stats = {"signatures_compiled": 0, "seconds": 0.0, "skipped": 0}
        budget = handler.cache_budget_bytes
        if budget is None:
            budget = accelerator.jit_config.persistent_cache_budget_bytes
        cache_dir = accelerator.jit_config.persistent_cache_dir
        self.cache = ManagedPersistentCache(cache_dir, budget) if cache_dir else None

    # -- auto-parallelism plan hook ---------------------------------------

    def note_plan(self, plan) -> None:
        """Warm toward the chosen plan's step shape (planner.py): the plan's
        sequence length and per-rank batch are grafted onto the fixed/auto
        bucket ladders so the very first real batch pads to the planned
        shape — the step the warmup compiles is the step training runs."""
        self._plan = plan
        seq = int(getattr(plan, "seq", 0) or 0)
        layout = getattr(plan, "layout", None) or {}
        dp = max(1, int(layout.get("dp_replicate", 1)) * int(layout.get("dp_shard", 1)))
        batch = int(getattr(plan, "per_chip_batch", 0) or 0) * int(
            getattr(plan, "n_devices", 0) or 0
        ) // dp
        h = self.handler
        for kind, dim in (("seq", seq), ("batch", batch)):
            if dim <= 0:
                continue
            ladder = h.seq_buckets if kind == "seq" else h.batch_buckets
            if ladder is not None and dim not in ladder:
                ladder.append(dim)
                ladder.sort()
            if self._auto_ladders and dim not in self._auto_ladders.get(kind, []):
                self._auto_ladders[kind] = sorted(self._auto_ladders[kind] + [dim])

    # -- bucketing ---------------------------------------------------------

    def _ladder(self, kind: str):
        h = self.handler
        return h.batch_buckets if kind == "batch" else h.seq_buckets

    def _auto_ladder(self, kind: str):
        if self._auto_ladders is None or self._auto_ladders.get("_n") != len(self.manifest or ()):
            dims = {"batch": set(), "seq": set()}
            for entry in (self.manifest.entries if self.manifest else []):
                spec_array_dims(entry.get("spec", {}), dims)
            self._auto_ladders = {
                "batch": sorted(dims["batch"]),
                "seq": sorted(dims["seq"]),
                "_n": len(self.manifest or ()),
            }
        return self._auto_ladders[kind]

    def bucket_for(self, n: int, kind: str = "seq") -> int:
        """Bucketed size for a raw dim of ``n``. Oversize (past ``max_bucket``
        or off the ladder) falls through to the TRUE size with a one-time
        warning — shipping the real shape beats crashing, but each distinct
        oversize shape costs a compile."""
        h = self.handler
        policy = h.buckets
        if policy is None:
            return n
        n = int(n)
        if policy == "fixed":
            ladder = self._ladder(kind)
            if not ladder:
                logger.warning_once(
                    "CompileKwargs(buckets='fixed') without %s_buckets — dim "
                    "left unbucketed.", kind
                )
                return n
            b = ladder_bucket(n, ladder)
        elif policy == "auto":
            ladder = self._auto_ladder(kind)
            b = ladder_bucket(n, ladder) if ladder else None
            if b is None:  # unseen size: fall back to the pow2 ladder
                b = pow2_bucket(n, h.min_bucket, h.max_bucket)
        else:  # pow2
            b = pow2_bucket(n, h.min_bucket, h.max_bucket)
        if b is None:
            self.oversize_events += 1
            logger.warning_once(
                "compile_manager: %s dim %d exceeds the largest bucket — "
                "shipping the true shape (one compile per distinct oversize "
                "shape). Raise max_bucket or extend the ladder.", kind, n
            )
            return n
        return b

    def bucket_pad(self, batch, batch_size_hint: Optional[int] = None):
        """Pad a host-side numpy batch to bucket shapes at the device
        boundary. Axis 0 is the batch dim on every array leaf (repo-wide
        convention); axis 1 of rank>=2 leaves is the sequence dim.

        - batch dim: padded up to ``batch_size_hint`` (the loader's full
          batch size — so the ragged final batch of a ``drop_last=False``
          epoch stops costing a one-off recompile) or, without a hint, to the
          policy bucket. ``batch_pad_mode="repeat"`` cycles real samples
          (the same semantics ``even_batches`` already gives the final batch;
          duplicate tails are trimmed by ``gather_for_metrics`` via
          ``GradientState.remainder``) and ``"zero"`` zero-fills.
        - sequence dim: zero-padded (``seq_pad_value``) up to its bucket.
          Only leaves whose axis-1 size equals the batch's REFERENCE
          sequence length (axis 1 of the first rank>=2 leaf — the same
          convention telemetry's token counter uses) participate: that keeps
          aligned leaves (ids/labels/positions) padded in lockstep while a
          ``(B, num_classes)`` or ``(B, 1)`` leaf riding in the same dict is
          left untouched.
        - ``emit_mask=True`` on dict batches ALWAYS adds a ``mask_key`` leaf
          (1.0 = real element) so the batch structure — and therefore the
          compiled signature — stays fixed whether or not padding occurred.
        """
        h = self.handler
        leaves = jax.tree_util.tree_leaves(batch)
        arrs = [l for l in leaves if getattr(l, "ndim", 0) >= 1]
        if not arrs:
            return batch
        raw_b = int(arrs[0].shape[0])
        if h.bucket_batch:
            if batch_size_hint is not None and raw_b <= int(batch_size_hint):
                target_b = int(batch_size_hint)
            else:
                target_b = self.bucket_for(raw_b, "batch")
        else:
            target_b = raw_b
        changed = target_b != raw_b
        first2 = next((a for a in arrs if a.ndim >= 2), None)
        ref_s = int(first2.shape[1]) if first2 is not None else None
        target_s = self.bucket_for(ref_s, "seq") if (h.bucket_seq and ref_s) else ref_s

        def _pad(arr):
            nonlocal changed
            if getattr(arr, "ndim", 0) < 1:
                return arr
            out = np.asarray(arr)
            if target_b > out.shape[0]:
                if h.batch_pad_mode == "repeat":
                    idx = np.arange(target_b) % out.shape[0]
                    out = np.take(out, idx, axis=0)
                else:
                    width = [(0, target_b - out.shape[0])] + [(0, 0)] * (out.ndim - 1)
                    out = np.pad(out, width, constant_values=0)
            if out.ndim >= 2 and out.shape[1] == ref_s and target_s > ref_s:
                width = [(0, 0), (0, target_s - ref_s)] + [(0, 0)] * (out.ndim - 2)
                out = np.pad(out, width, constant_values=h.seq_pad_value)
                changed = True
            return out

        padded = jax.tree.map(_pad, batch)
        if changed:
            self.pad_events += 1
        if h.emit_mask and isinstance(padded, dict):
            if ref_s is not None:
                mask = np.zeros((target_b, target_s), np.float32)
                mask[:raw_b, :ref_s] = 1.0
            else:
                mask = np.zeros((target_b,), np.float32)
                mask[:raw_b] = 1.0
            padded[h.mask_key] = mask
        return padded

    # -- signature observation (hot path when enabled) ---------------------

    def observe(self, batch) -> None:
        """Record the (post-bucketing, global) batch signature; one manifest
        line per new digest. Called by the prepared step wrapper."""
        digest = batch_digest(batch)
        if digest in self._seen:
            return
        self._seen.add(digest)
        if self.manifest is not None:
            self.manifest.record(digest, tree_to_spec(batch))

    def record_digest(self, digest: str, batch) -> None:
        """Watchdog bridge entry point (digest already computed)."""
        if digest in self._seen:
            return
        self._seen.add(digest)
        if self.manifest is not None:
            self.manifest.record(digest, tree_to_spec(batch))

    # -- generation signatures (decode loops) ------------------------------

    def record_generation_signature(self, plan: str, batch: int, prompt_len: int,
                                    max_new_tokens: int, settings: Optional[dict] = None) -> None:
        """Record one ``generate()`` call signature (post-bucketing prompt
        shape + sampling settings) so :meth:`warmup_generation` can compile
        decode loops before the first request on a restart."""
        settings = dict(settings or {})
        digest = "gen:{}:{}x{}+{}:{}".format(
            plan, int(batch), int(prompt_len), int(max_new_tokens),
            "|".join(f"{k}={settings[k]}" for k in sorted(settings)),
        )
        if digest in self._seen:
            return
        self._seen.add(digest)
        if self.manifest is not None:
            spec = {
                "kind": "generation", "plan": plan, "batch": int(batch),
                "prompt_len": int(prompt_len),
                "max_new_tokens": int(max_new_tokens), "settings": settings,
            }
            self.manifest.record(digest, spec)

    def warmup_generation(self, model, generate_fn=None) -> int:
        """Compile every recorded generation signature for ``model``'s plan
        NOW (zero-filled dummy prompts through ``generate``) — the decode
        analog of the train-step warmup. Returns the number of signatures
        compiled; bad entries are skipped with a warning."""
        if self.manifest is None:
            return 0
        if generate_fn is None:
            from .generation import generate as generate_fn
        plan = type(model.module).__name__
        compiled = 0
        t0 = time.perf_counter()
        for entry in self.manifest.entries:
            spec = entry.get("spec") or {}
            if spec.get("kind") != "generation" or spec.get("plan") != plan:
                continue
            settings = spec.get("settings") or {}
            try:
                ids = np.zeros((spec["batch"], spec["prompt_len"]), np.int32)
                kwargs = {
                    k: settings.get(k)
                    for k in ("temperature", "top_k", "top_p", "eos_token_id",
                              "pad_token_id")
                    if settings.get(k) is not None
                }
                if settings.get("masked"):
                    kwargs["attention_mask"] = np.ones_like(ids)
                generate_fn(model, ids, max_new_tokens=spec["max_new_tokens"],
                            **kwargs)
                compiled += 1
            except Exception as e:  # warmup must never kill serving/inference
                logger.warning(
                    "compile_manager: generation warmup failed for %s: %s: %s",
                    entry.get("digest", "?")[:80], type(e).__name__, e,
                )
        if compiled:
            seconds = time.perf_counter() - t0
            self.warmup_stats["signatures_compiled"] += compiled
            self.warmup_stats["seconds"] += seconds
            logger.info(
                "compile_manager: warmed %d generation signature(s) in %.2fs "
                "— the first request will not pay these compiles.",
                compiled, seconds,
            )
        return compiled

    def prefill_ladder(self, max_len: int, min_chunk: int = 16,
                       max_chunk: int = 256) -> list:
        """Chunk-size ladder for the serving engine's chunked prefill: the
        handler's explicit seq buckets when the policy is ``fixed``, else
        the pow2 ladder clipped to ``[min_chunk, min(max_chunk, max_len)]``
        — so prefill executables and bucketed batch shapes share rungs."""
        from .serving import default_prefill_ladder

        h = self.handler
        if h.buckets == "fixed" and h.seq_buckets:
            rungs = sorted({int(x) for x in h.seq_buckets if int(x) <= max_len})
            if rungs:
                return rungs
        lo = max(min_chunk, h.min_bucket)
        hi = min(max_chunk, h.max_bucket) if h.max_bucket else max_chunk
        return default_prefill_ladder(max_len, lo, max(lo, hi))

    # -- step registration + warmup ----------------------------------------

    def register_step(self, jitted, slot: int = 0, label: str = "train_step",
                      warmable: bool = True) -> None:
        """Called by ``prepare_train_step`` with the underlying jitted step.
        When warmup is on, every known manifest signature is compiled NOW —
        before step 0 — so restarts skip first-step compile stalls."""
        entry = {"fn": jitted, "slot": slot, "label": label,
                 "warmable": warmable, "warmed": set()}
        self._steps.append(entry)
        if self.handler.warmup != "off":
            self._warmup_entry(entry)

    def warmup(self) -> dict:
        """(Re-)warm every registered step against the current manifest.
        Idempotent: signatures already warmed for a step are skipped, so a
        second call compiles nothing."""
        for entry in self._steps:
            self._warmup_entry(entry)
        return dict(self.warmup_stats)

    def invalidate_steps(self) -> int:
        """Forget every warmed signature (elastic plan migration: the old
        executables were specialized to the previous mesh/shardings). The
        steps stay registered — jit retraces them for the new layout on the
        next call, and ``warmup()`` re-warms every manifest signature.
        Returns the number of executables dropped from the jit caches."""
        dropped = 0
        for entry in self._steps:
            fn = entry["fn"]
            try:
                dropped += int(fn._cache_size())
                fn.clear_cache()
            except Exception:
                pass
            entry["warmed"] = set()
        return dropped

    def _batch_sharding(self, ndim: int):
        from .parallel.sharding import batch_partition_spec

        acc = self.accelerator
        spec = batch_partition_spec(ndim, acc.state.parallelism_config)
        return jax.sharding.NamedSharding(acc.mesh, spec)

    def _build_batch(self, spec, abstract: bool):
        """Manifest spec → device batch: zero-filled global arrays for
        ``execute`` warmup, sharded ``ShapeDtypeStruct``s for ``aot``. The
        sharding MUST match what the loader ships (same NamedSharding) or the
        warmed executable would miss on the first real batch."""
        acc = self.accelerator

        def _leaf(shape, dtype):
            sharding = self._batch_sharding(len(shape))
            if abstract:
                return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)
            arr = np.zeros(shape, np.dtype(dtype))
            if acc.num_processes > 1:
                per = shape[0] // acc.num_processes
                if per * acc.num_processes != shape[0]:
                    raise ValueError(f"batch dim {shape[0]} not divisible by world")
                local = arr[: per] if per else arr
                return jax.make_array_from_process_local_data(sharding, local)
            return jax.device_put(arr, sharding)

        return spec_map_leaves(spec, _leaf)

    def _warmup_entry(self, entry: dict) -> None:
        if not entry["warmable"] or self.manifest is None or not len(self.manifest):
            return
        acc = self.accelerator
        states = getattr(acc, "_train_states", None)
        if not states or entry["slot"] >= len(states):
            return
        state = states[entry["slot"]]
        mode = self.handler.warmup
        pending = [
            e for e in self.manifest.entries
            if e["digest"] not in entry["warmed"]
            # Generation signatures belong to warmup_generation (they need a
            # model, not a train state).
            and (e.get("spec") or {}).get("kind") != "generation"
        ]
        if not pending:
            return
        t0 = time.perf_counter()
        compiled = 0
        work = None  # execute mode: one donated-safe copy, threaded across signatures
        for mentry in pending:
            try:
                batch = self._build_batch(mentry["spec"], abstract=(mode == "aot"))
            except (ValueError, TypeError) as e:
                entry["warmed"].add(mentry["digest"])  # never retry a bad spec
                self.warmup_stats["skipped"] += 1
                logger.warning_once(
                    "compile_manager: manifest signature %s is not warmable "
                    "(%s) — skipped.", mentry["digest"][:80], str(e)
                )
                continue
            try:
                if mode == "aot":
                    state_abs = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=getattr(x, "sharding", None)
                        )
                        if hasattr(x, "shape")
                        else x,
                        state,
                    )
                    entry["fn"].lower(state_abs, batch).compile()
                else:
                    if work is None:
                        # jnp.copy, not device_put-to-same-sharding: the
                        # latter aliases, and donation would then invalidate
                        # the REAL train state's buffers.
                        work = jax.tree.map(
                            lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
                            state,
                        )
                    for _ in range(max(1, self.handler.warmup_calls)):
                        work, _metrics = entry["fn"](work, batch)
            except Exception as e:  # warmup must never kill training
                logger.warning(
                    "compile_manager: warmup failed for signature %s: %s: %s",
                    mentry["digest"][:80], type(e).__name__, e,
                )
                continue
            entry["warmed"].add(mentry["digest"])
            compiled += 1
        if work is not None:
            try:
                jax.block_until_ready(work)  # honest warmup timing
            except Exception:
                pass
        seconds = time.perf_counter() - t0
        self.warmup_stats["signatures_compiled"] += compiled
        self.warmup_stats["seconds"] += seconds
        if compiled:
            logger.info(
                "compile_manager: warmed %d signature(s) for %s in %.2fs "
                "(mode=%s) — step 0 will not pay these compiles.",
                compiled, entry["label"], seconds, mode,
            )

    # -- reporting ---------------------------------------------------------

    def executable_count(self) -> int:
        """Total executables across registered step fns (jit dispatch-cache
        sizes) — the number the acceptance bar caps at ``len(buckets)``."""
        total = 0
        for entry in self._steps:
            size = _cache_size(entry["fn"])
            if size:
                total += size
        return total

    def cache_stats(self) -> Optional[dict]:
        if self.cache is None:
            return None
        return self.cache.stats(compile_events=self.executable_count())

    def summary(self) -> dict:
        out = {
            "bucket_policy": self.handler.buckets,
            "executables": self.executable_count(),
            "manifest_signatures": len(self.manifest) if self.manifest else 0,
            "pad_events": self.pad_events,
            "oversize_events": self.oversize_events,
            "warmup": dict(self.warmup_stats),
        }
        cache = self.cache_stats()
        if cache is not None:
            out["persistent_cache"] = cache
        return out

    def close(self) -> None:
        if self.cache is not None:
            self.cache.prune()
