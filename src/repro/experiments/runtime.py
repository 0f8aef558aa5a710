"""Resumable, checkpointed sweep runtime (DESIGN.md §8).

``run_sweep`` is fire-and-forget: a week-long frontier grid that dies at
hour 60 restarts from zero.  ``run_sweep_resumable`` executes the *same
plan* (``repro.experiments.sweep.plan_sweep``) in chunk-granular
segments — ``SweepSpec.chunk_size`` runs per device per segment, the
same map-over-vmap unit the engine already chunks by — and checkpoints
each completed segment's result pytree through ``repro.checkpoint.store``
(atomic npz: write-to-temp + rename), tagged with a content hash of the
spec, the input arrays and the chunk layout.  A killed sweep re-invoked
with the same ``store_dir`` loads the finished segments and computes only
the rest; because vmapped segment execution is bitwise identical to the
single-call path on this backend, the resumed result equals the
uninterrupted ``run_sweep`` result bit for bit
(tests/test_runtime_resume.py asserts it for full and summary traces).

Checkpoint writes are asynchronous: segment k+1 is dispatched to the
device before segment k's arrays are fetched and written, so the host
I/O overlaps device execution (a single writer thread preserves write
order; jax's async dispatch does the rest).

Finished sweeps land in the append-only ``SweepStore``
(``repro.experiments.store``), whose entries the device-free query
service (``repro.experiments.query`` / ``serve_sweeps``) answers
trigger-threshold questions from.  ``run_sweep_extend`` closes the loop:
asked for a λ grid that is partially cached, it computes only the
missing λ columns, merges them with the store's family entries, and
persists the union; ``sweep_or_load`` is the store-first entry point the
figure benchmarks build on (DESIGN.md §9).  Finished chunk dirs are
recovery state — ``gc_finished`` reclaims them once the summary record
is committed (refusing while the ``INCOMPLETE`` resume lock exists).
"""

from __future__ import annotations

import concurrent.futures
import contextvars
import dataclasses
import functools
import hashlib
import json
import os
import re
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, spans
from repro.checkpoint import store as ckpt
from repro.core.algorithm1 import InnerTrace, ProblemTerms, SummaryTrace
from repro.core import vfa as vfa_lib
from repro.experiments import store as store_lib
from repro.experiments.sweep import (
    SweepPlan,
    SweepResult,
    SweepSpec,
    exec_plan_segment,
    finalize_sweep,
    plan_sweep,
    segment_shapes,
)

_CHUNK_RE = re.compile(r"chunk_(\d{6})\.npz$")
_MANIFEST = "manifest.json"
_INCOMPLETE = "INCOMPLETE"
_FORMAT_VERSION = 1


def _chunk_path(store_dir: str, index: int) -> str:
    return os.path.join(store_dir, f"chunk_{index:06d}.npz")


def _tree_digest(h, tree) -> None:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    h.update(str(treedef).encode())
    for leaf in leaves:
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())


def inputs_digest(sampler, w0, problem=None, param_sets=None,
                  env_sets=None, fleet_sets=None) -> str:
    """Content digest of everything *outside* the spec that shapes results.

    The spec hash alone cannot tell two sweeps apart when they differ in
    ``w0``, the fleet's stacked sampler params, the exact problem, the
    env family, or the zipped per-env fleet stacks — this digest rides in
    every chunk checkpoint and store entry so a resume (or a merge)
    against the wrong inputs raises instead of silently mixing runs.  The
    sampler *function* is assumed pure and identified by the arrays it
    consumes (the repo-wide convention).
    """
    h = hashlib.sha256()
    terms = (problem if isinstance(problem, ProblemTerms)
             else ProblemTerms.from_problem(problem) if problem is not None
             else None)
    _tree_digest(h, jnp.asarray(w0))
    # with param_sets or fleet_sets the engine ignores sampler.params
    # entirely, so two samplers differing only there must digest identically
    _tree_digest(h, None if (param_sets is not None or fleet_sets is not None)
                 else getattr(sampler, "params", None))
    _tree_digest(h, terms)
    _tree_digest(h, param_sets)
    if env_sets is not None:
        _tree_digest(h, env_sets.params)
        _tree_digest(h, getattr(env_sets, "terms", None))
    else:
        _tree_digest(h, None)
    _tree_digest(h, fleet_sets)
    return h.hexdigest()


def _exec_hash(spec_hash_: str, in_digest: str, plan: SweepPlan) -> str:
    """Identity of one chunked execution: results + chunk layout.

    ``chunk_size`` is excluded from the *spec* hash (results are bitwise
    independent of it) but segment boundaries must match for chunk files
    to be reusable, so the layout is hashed separately here.
    """
    blob = json.dumps({
        "version": _FORMAT_VERSION,
        "spec_hash": spec_hash_,
        "inputs_digest": in_digest,
        "segment_runs": plan.segment_runs,
        "padded_runs": plan.padded_runs,
        "num_devices": plan.num_devices,
        "batching": plan.spec.batching,
        # bitwise identity only holds within one XLA build/backend: a
        # resume after a jax upgrade must refuse the old chunks loudly
        # rather than assemble a result no single version would produce
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
    }, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


def _segment_template(plan: SweepPlan):
    """Zero-filled host pytree matching one segment's output (via
    ``eval_shape`` — no device computation)."""
    shapes = segment_shapes(plan)
    return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_segment(acc, seg, start):
    """Write one segment's rows into the run-stacked accumulator, in place.

    ``acc`` is DONATED: XLA aliases every accumulator buffer to the
    corresponding output (shapes/dtypes match exactly, so the aliasing is
    total — asserted structurally via ``launch.hlo_analysis
    .donated_aliases``), which makes each segment boundary an in-place
    update instead of a full copy of the run-stacked state.  The caller
    must never touch the donated ``acc`` again — reading it raises
    ``RuntimeError`` (the use-after-donate guard test relies on this).
    ``start`` is traced so every segment shares one compiled program.
    """
    return jax.tree.map(
        lambda a, s: jax.lax.dynamic_update_slice_in_dim(a, s, start, 0),
        acc, seg)


def _result_accumulator(plan: SweepPlan):
    """Zero device pytree shaped like the full padded run-stacked result."""
    shapes = segment_shapes(plan)
    return jax.tree.map(
        lambda s: jnp.zeros((plan.padded_runs,) + s.shape[1:], s.dtype),
        shapes)


def _write_manifest(store_dir: str, meta: dict) -> None:
    path = os.path.join(store_dir, _MANIFEST)
    if os.path.exists(path):
        with open(path) as f:
            prev = json.load(f)
        if prev.get("exec_hash") != meta["exec_hash"]:
            raise ValueError(
                f"{store_dir} already holds chunks of a different sweep "
                f"(exec_hash {prev.get('exec_hash')!r} != "
                f"{meta['exec_hash']!r}); use a fresh store_dir per sweep")
        if meta.get("summary_store") in (None, prev.get("summary_store")):
            return
        # resume added/changed the summary store: record it for gc_finished
        meta = {**prev, "summary_store": meta["summary_store"]}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(meta, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _note_summary_store(store_dir: str, root: str) -> None:
    """Record (post hoc) which summary store holds this sweep's final
    record — what ``gc_finished`` verifies against by default."""
    path = os.path.join(store_dir, _MANIFEST)
    if not os.path.isfile(path):
        return
    with open(path) as f:
        manifest = json.load(f)
    if manifest.get("summary_store") == root:
        return
    manifest["summary_store"] = root
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def completed_chunks(store_dir: str, exec_hash: str) -> dict[int, str]:
    """Map of segment index -> path for valid finished chunk checkpoints."""
    out: dict[int, str] = {}
    if not os.path.isdir(store_dir):
        return out
    for name in os.listdir(store_dir):
        m = _CHUNK_RE.match(name)
        if not m:
            continue
        path = os.path.join(store_dir, name)
        try:
            meta = ckpt.load_metadata(path)
        except ckpt.CorruptCheckpointError as e:
            # torn/corrupt chunk: rename it aside (never silently reuse a
            # name a later save would collide with) and recompute
            faults.quarantine_path(path, f"unreadable chunk: {e}")
            continue
        except Exception:
            continue                      # foreign file: ignore
        if meta.get("exec_hash") == exec_hash:
            out[int(m.group(1))] = path
    return out


@spans.span("runtime.run")
def run_sweep_resumable(
    spec: SweepSpec,
    sampler,
    w0,
    problem: Optional[Union[vfa_lib.VFAProblem, ProblemTerms]] = None,
    *,
    store_dir: str,
    param_sets=None,
    env_sets=None,
    fleet_sets=None,
    mesh=None,
    state_init_fn=None,
    summary_store: Optional[Union[str, store_lib.SweepStore]] = None,
    on_chunk=None,
    durable: bool = False,
) -> SweepResult:
    """``run_sweep``, executed in checkpointed segments so it can resume.

    Args (beyond ``run_sweep``'s):
      store_dir:     directory for the chunk checkpoints + manifest.  One
                     sweep per directory; re-invoking with the same inputs
                     resumes from the finished chunks, bitwise identical
                     to an uninterrupted run.
      summary_store: optional ``SweepStore`` (or its root path): on
                     completion the finished ``SweepResult`` is appended
                     there, keyed by the spec hash, ready for the query
                     service.
      on_chunk:      optional ``fn(index, total, restored: bool)`` called
                     when a segment is restored from its checkpoint
                     (restored=True), or when a computed segment has been
                     dispatched and queued for checkpointing — NOT a
                     durability signal: a chunk is only guaranteed on
                     disk once this function returns.
      durable:       fsync chunk files' containing directory after each
                     atomic rename (and the summary-store entry dir on
                     commit) — rename alone does not survive power loss.
                     Off by default so tests stay fast.

    A chunk that fails its restore (torn write, bit flip — checksums in
    every chunk's npz sidecar are re-verified) is **quarantined**: renamed
    aside with a stderr log, then recomputed in place, so the resumed
    result is still bitwise identical to the uninterrupted run.  Corrupt
    chunks are never silently merged.

    Segment granularity is ``spec.chunk_size`` runs per device
    (``SweepPlan.segment_runs``); with ``chunk_size=None`` the whole grid
    is one segment — it still checkpoints, but cannot resume mid-grid.

    While the sweep runs (and after a crash) the dir carries an
    ``INCOMPLETE`` marker, removed only on successful completion — the
    resume lock ``gc_finished`` refuses to collect past.

    The call's host work is recorded as the span ``runtime.run`` with
    children ``sweep.plan``, one ``runtime.segment`` per segment dispatched
    or restored (``restored``), ``runtime.checkpoint`` per chunk written
    (on the writer thread, under the caller's call id) and
    ``runtime.drain``, the final wait on pending writes (``repro.spans``).
    """
    with spans.span("sweep.plan"):
        plan = plan_sweep(spec, sampler, w0, problem, param_sets=param_sets,
                          env_sets=env_sets, fleet_sets=fleet_sets,
                          mesh=mesh, state_init_fn=state_init_fn)
    sh = store_lib.spec_hash(spec)
    in_digest = inputs_digest(sampler, w0, problem=problem,
                              param_sets=param_sets, env_sets=env_sets,
                              fleet_sets=fleet_sets)
    exec_hash = _exec_hash(sh, in_digest, plan)
    segments = plan.segments()

    if summary_store is not None and not isinstance(summary_store,
                                                    store_lib.SweepStore):
        summary_store = store_lib.SweepStore(summary_store)
    os.makedirs(store_dir, exist_ok=True)
    _write_manifest(store_dir, {
        "version": _FORMAT_VERSION,
        "spec": store_lib.spec_payload(spec),
        "spec_hash": sh,
        "inputs_digest": in_digest,
        "exec_hash": exec_hash,
        "axes": list(plan.axes),
        "grid_shape": list(plan.gs),
        "num_segments": len(segments),
        "segment_runs": plan.segment_runs,
        "padded_runs": plan.padded_runs,
        # retention/GC: lets gc_finished verify the final merged record
        # without being handed the store again
        "summary_store": (summary_store.root
                          if summary_store is not None else None),
    })
    with faults.scope("runtime.lock"):
        with open(os.path.join(store_dir, _INCOMPLETE), "w") as f:
            f.write(exec_hash)
    done = completed_chunks(store_dir, exec_hash)
    template = _segment_template(plan) if done else None

    def _save_chunk(path: str, index: int, out) -> None:
        # Runs on the writer thread: np.asarray blocks until the device
        # finishes this segment, while the main thread has already
        # dispatched the next one — checkpoint I/O overlaps execution.
        with spans.span("runtime.checkpoint", index=index):
            host = jax.tree.map(np.asarray, out)
            ckpt.save(path, host, durable=durable, metadata={
                "exec_hash": exec_hash, "spec_hash": sh,
                "inputs_digest": in_digest, "segment_index": index,
                "segment": list(segments[index]),
                "grid_coords": {"start": segments[index][0],
                                "stop": segments[index][1],
                                "axes": list(plan.axes),
                                "grid_shape": list(plan.gs)},
            })

    # Segment results accumulate in place into one run-stacked pytree: the
    # accumulator is DONATED to the scatter at every segment boundary (XLA
    # aliases it to the output — no copy of the run-stacked state, unlike
    # the concatenate-at-the-end assembly this replaces, which kept every
    # segment alive and then materialized the full result a second time).
    # A single segment skips the accumulator entirely.
    single = None
    acc = _result_accumulator(plan) if len(segments) > 1 else None
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="sweep-ckpt") as pool:
        pending = []
        for i, (a, b) in enumerate(segments):
            with spans.span("runtime.segment", index=i) as attrs:
                seg = None
                if i in done:
                    try:
                        restored, meta = ckpt.restore(done[i], template)
                    except ckpt.CorruptCheckpointError as e:
                        # checksum/decode failure on a finished chunk:
                        # rename it aside and recompute the segment — the
                        # resumed result stays bitwise identical to a clean
                        # run, and corrupt bytes are never merged
                        faults.quarantine_path(done[i], str(e))
                        del done[i]
                    else:
                        if tuple(meta["segment"]) != (a, b):
                            raise ValueError(
                                f"chunk {done[i]} covers runs "
                                f"{meta['segment']}, expected [{a}, {b}) — "
                                f"stale store_dir?")
                        seg = restored
                        if on_chunk is not None:
                            on_chunk(i, len(segments), True)
                attrs["restored"] = seg is not None
                if seg is None:
                    seg = exec_plan_segment(plan, a, b)   # async dispatch
                    # the writer closure holds the only other reference to
                    # seg; it is submitted BEFORE the scatter so the
                    # checkpoint bytes are fetched from the segment output,
                    # never from acc.  The copied context carries this
                    # call's span into the writer thread.
                    pending.append(pool.submit(
                        contextvars.copy_context().run, _save_chunk,
                        _chunk_path(store_dir, i), i, seg))
                    if on_chunk is not None:
                        on_chunk(i, len(segments), False)
                if acc is None:
                    single = seg
                else:
                    acc = _scatter_segment(acc, seg, jnp.int32(a))
        with spans.span("runtime.drain", pending=len(pending)):
            for f in pending:
                f.result()                             # re-raise I/O errors

    flat = single if acc is None else acc
    result = finalize_sweep(plan, flat)

    if summary_store is not None:
        store_result(summary_store, spec, result, inputs_digest_=in_digest,
                     durable=durable)
    # every chunk is durable and the summary (if requested) committed:
    # release the resume lock so gc_finished may collect the chunk dir.
    # A crash before this remove leaves a committed entry under a live
    # lock — the stale-lock rules in gc_finished/_lock_is_stale, and a
    # re-run simply restores every chunk and re-puts byte-identically.
    with faults.scope("runtime.unlock"):
        os.remove(os.path.join(store_dir, _INCOMPLETE))
    return result


def _lock_is_stale(store_dir: str, lock_path: str,
                   store: Optional[Union[str, store_lib.SweepStore]]) -> bool:
    """True iff an INCOMPLETE lock belongs to a provably *finished* sweep.

    The completion sequence is: write every chunk -> commit the summary
    store entry -> remove the lock.  A crash between the last two steps
    leaves the lock on a sweep whose deliverable is already durable.  The
    lock is stale only when all three completion facts hold: the lock's
    exec hash matches the manifest (it is THIS plan's lock, not a crashed
    resume under different statics), every manifest segment has a durable
    chunk, and the summary store carries the manifest's spec hash with the
    matching inputs digest.  Anything less — unreadable state included —
    is treated as live.
    """
    try:
        with open(lock_path) as f:
            lock_hash = f.read().strip()
        manifest_path = os.path.join(store_dir, _MANIFEST)
        with open(manifest_path) as f:
            manifest = json.load(f)
        if lock_hash != manifest.get("exec_hash"):
            return False
        done = completed_chunks(store_dir, manifest["exec_hash"])
        if sorted(done) != list(range(manifest["num_segments"])):
            return False
        root = store if store is not None else manifest.get("summary_store")
        if root is None:
            return False
        s = (root if isinstance(root, store_lib.SweepStore)
             else store_lib.SweepStore(root))
        sh = manifest["spec_hash"]
        if not s.has(sh):
            return False
        return (s.get(sh).extra.get("inputs_digest")
                == manifest.get("inputs_digest"))
    except (OSError, ValueError, KeyError):
        return False


def gc_finished(store_dir: str,
                store: Optional[Union[str, store_lib.SweepStore]] = None,
                ) -> dict:
    """Retention/GC: delete a *finished* sweep's chunk checkpoints.

    Chunk files are recovery state, not results — once the sweep's final
    merged record is committed to the summary ``SweepStore`` they only
    cost disk.  ``gc_finished`` removes them (and the manifest, and the
    dir when it is then empty) after verifying, in order:

    * no ``INCOMPLETE`` resume lock is present (the sweep is mid-run or
      crashed; resuming to completion clears it) — else ``RuntimeError``.
      Exception: a *stale* lock.  ``run_sweep_resumable`` commits the
      summary-store entry *before* removing the lock, so a crash in that
      window leaves a fully-finished sweep locked forever.  When the lock
      carries the manifest's exec hash, every manifest chunk is durable,
      AND the summary store holds the final record with the matching
      inputs digest, the lock is provably stale and is reclaimed;
    * the summary store (``store=``, defaulting to the root recorded in
      the manifest when the sweep ran with ``summary_store=``) holds an
      entry for the manifest's spec hash with the same inputs digest —
      else ``LookupError``.

    Idempotent: a second call, or a call on a dir that never existed,
    returns ``{"collected": False, ...}`` without touching anything.
    Returns GC stats (files and bytes freed).
    """
    manifest_path = os.path.join(store_dir, _MANIFEST)
    if not os.path.isdir(store_dir) or not os.path.isfile(manifest_path):
        chunks = [n for n in (os.listdir(store_dir)
                              if os.path.isdir(store_dir) else [])
                  if _CHUNK_RE.match(n)]
        if chunks:
            raise LookupError(
                f"{store_dir} holds chunk files but no manifest — not a "
                "sweep this runtime finished; refusing to delete")
        return {"collected": False, "files": 0, "bytes": 0,
                "reason": "nothing to collect"}
    lock_path = os.path.join(store_dir, _INCOMPLETE)
    if os.path.exists(lock_path):
        if not _lock_is_stale(store_dir, lock_path, store):
            raise RuntimeError(
                f"{store_dir} carries the INCOMPLETE resume lock — the sweep "
                "is running or crashed mid-run; resume it to completion (or "
                "delete the dir manually) before collecting")
        # crash landed between the summary-store commit and the lock
        # removal: the final record is committed and every chunk durable,
        # so finish the interrupted release and proceed with collection
        os.remove(lock_path)
    with open(manifest_path) as f:
        manifest = json.load(f)
    if store is None:
        store = manifest.get("summary_store")
        if store is None:
            raise LookupError(
                f"{store_dir} ran without summary_store= and no store= was "
                "passed — cannot verify the final record is committed")
    if not isinstance(store, store_lib.SweepStore):
        store = store_lib.SweepStore(store)
    sh = manifest["spec_hash"]
    if not store.has(sh):
        raise LookupError(
            f"summary store {store.root} has no entry {sh} — the final "
            "merged record is not committed; refusing to delete chunks")
    entry_digest = store.get(sh).extra.get("inputs_digest")
    if entry_digest != manifest["inputs_digest"]:
        raise LookupError(
            f"store entry {sh} was computed from different inputs "
            f"({entry_digest} != {manifest['inputs_digest']}) — refusing "
            "to treat it as this sweep's final record")
    files, freed = 0, 0
    with faults.scope("runtime.gc"):
        for name in sorted(os.listdir(store_dir)):
            if _CHUNK_RE.match(name) or name == _MANIFEST:
                path = os.path.join(store_dir, name)
                freed += os.path.getsize(path)
                os.remove(path)
                files += 1
    if not os.listdir(store_dir):
        os.rmdir(store_dir)
    return {"collected": True, "files": files, "bytes": freed,
            "spec_hash": sh}


# ---------------------------------------------------------------------------
# SweepResult <-> SweepStore conversion (the jax-side half; the store and
# the query service stay numpy-only).
# ---------------------------------------------------------------------------


def result_arrays(result: SweepResult) -> dict[str, np.ndarray]:
    """Flatten a ``SweepResult`` to the store's flat numpy dict."""
    out = {f"trace/{k}": np.asarray(v)
           for k, v in result.trace._asdict().items() if v is not None}
    if result.j_final is not None and not isinstance(result.trace,
                                                     SummaryTrace):
        out["j_final"] = np.asarray(result.j_final)
    return out


def arrays_to_result(entry: store_lib.StoredSweep) -> SweepResult:
    """Rebuild the jax-side ``SweepResult`` from a store entry."""
    kind = entry.extra.get("trace_kind", "summary")
    cls = InnerTrace if kind == "full" else SummaryTrace
    vals = {name: None for name in cls._fields}
    for k, v in entry.arrays.items():
        if k.startswith("trace/"):
            vals[k[len("trace/"):]] = jnp.asarray(v)
    trace = cls(**vals)
    if kind == "full":
        j_final = (jnp.asarray(entry.arrays["j_final"])
                   if "j_final" in entry.arrays else None)
    else:
        j_final = trace.j_final
    return SweepResult(trace=trace, comm_rate=trace.comm_rate,
                       j_final=j_final, axes=tuple(entry.axes))


def store_result(store: store_lib.SweepStore, spec: SweepSpec,
                 result: SweepResult, *,
                 inputs_digest_: Optional[str] = None,
                 extra: Optional[dict] = None,
                 durable: bool = False) -> str:
    """Append a finished sweep to the summary store; returns its hash."""
    kind = "full" if isinstance(result.trace, InnerTrace) else "summary"
    meta = {"trace_kind": kind}
    if inputs_digest_ is not None:
        meta["inputs_digest"] = inputs_digest_
    meta.update(extra or {})
    return store.put(spec, result_arrays(result), result.axes, extra=meta,
                     durable=durable)


def _select_lambdas(entry: store_lib.StoredSweep,
                    lambdas: tuple[float, ...]) -> store_lib.StoredSweep:
    """Restrict an entry to the requested λ values (requested order)."""
    lam_axis = entry.axes.index("lam")
    have = entry.lambdas
    idx = []
    for lam in lambdas:
        if float(lam) not in have:
            raise KeyError(f"λ={lam} not in entry (has {have})")
        idx.append(have.index(float(lam)))
    arrays = {k: np.take(v, idx, axis=lam_axis)
              for k, v in entry.arrays.items()}
    spec = dict(entry.spec)
    spec[store_lib.MERGE_FIELD] = [float(l) for l in lambdas]
    return store_lib.StoredSweep(
        spec=spec, spec_hash=store_lib.spec_hash(spec),
        family_hash=entry.family_hash, axes=entry.axes, arrays=arrays,
        extra=dict(entry.extra))


def run_sweep_extend(
    store: Union[str, store_lib.SweepStore],
    spec: SweepSpec,
    sampler,
    w0,
    problem: Optional[Union[vfa_lib.VFAProblem, ProblemTerms]] = None,
    *,
    param_sets=None,
    env_sets=None,
    fleet_sets=None,
    mesh=None,
    state_init_fn=None,
    store_dir: Optional[str] = None,
    extra: Optional[dict] = None,
) -> SweepResult:
    """Grid extension: compute only the λ cells the store does not have.

    Looks up the spec's experiment family (same everything-but-λ, same
    input digest) in ``store``, runs a sub-sweep over just the missing λ
    values (resumable when ``store_dir`` is given), appends it, and
    returns the ``SweepResult`` for exactly the requested λ grid.  The
    family's union is merged in memory (never persisted as its own
    entry); the *requested* grid is persisted so ``store.get(spec)``
    answers directly — deliberate duplication of cached columns, traded
    for hash-addressable results (skip it by querying the family via
    ``store.merged`` instead).  A fully-cached request touches no device.

    ``extra`` key/values land in the persisted entries' metadata (e.g.
    ``{"figure": "fig2"}`` — what the report pipeline renders by).
    """
    if not isinstance(store, store_lib.SweepStore):
        store = store_lib.SweepStore(store)
    in_digest = inputs_digest(sampler, w0, problem=problem,
                              param_sets=param_sets, env_sets=env_sets,
                              fleet_sets=fleet_sets)
    # A corrupt family member discovered while merging is quarantined and
    # its λ columns recomputed — each retry removes one entry from the
    # family, so the loop is bounded by the family size.
    attempt = 0
    while True:
        missing = store.missing_lambdas(spec, inputs_digest=in_digest)
        if missing:
            sub = dataclasses.replace(spec, lambdas=tuple(missing))
            # one store_dir holds one chunk layout: a quarantine-retry
            # sub-sweep (different λ set, different exec hash) must not
            # reuse the dir the first sub-sweep claimed
            if store_dir is not None and attempt == 0:
                result = run_sweep_resumable(
                    sub, sampler, w0, problem, store_dir=store_dir,
                    param_sets=param_sets, env_sets=env_sets,
                    fleet_sets=fleet_sets, mesh=mesh,
                    state_init_fn=state_init_fn)
            else:
                from repro.experiments.sweep import run_sweep
                result = run_sweep(sub, sampler, w0, problem,
                                   param_sets=param_sets, env_sets=env_sets,
                                   fleet_sets=fleet_sets, mesh=mesh,
                                   state_init_fn=state_init_fn)
            store_result(store, sub, result, inputs_digest_=in_digest,
                         extra=extra)
            if store_dir is not None:
                # the sub-sweep's record is committed (with the figure
                # extras, which is why run_sweep_resumable does not write it
                # itself): note the store root so gc_finished can verify
                # unaided
                _note_summary_store(store_dir, store.root)
        try:
            merged = store.merged(spec, inputs_digest=in_digest)
            break
        except store_lib.StoreCorruptError as e:
            store.quarantine(e.spec_hash, e.reason)
            attempt += 1
    entry = _select_lambdas(merged, tuple(float(l) for l in spec.lambdas))
    if extra:
        entry = dataclasses.replace(entry, extra={**entry.extra, **extra})
    # make the exact requested spec addressable by hash in the store
    if not store.has(entry.spec_hash):
        store.put(entry.spec, entry.arrays, entry.axes, extra=entry.extra)
    return arrays_to_result(entry)


def sweep_or_load(
    store: Union[str, store_lib.SweepStore],
    spec: SweepSpec,
    sampler,
    w0,
    problem: Optional[Union[vfa_lib.VFAProblem, ProblemTerms]] = None,
    *,
    param_sets=None,
    env_sets=None,
    fleet_sets=None,
    mesh=None,
    state_init_fn=None,
    store_dir: Optional[str] = None,
    extra: Optional[dict] = None,
) -> SweepResult:
    """Store-first sweep: load when cached, compute only what is missing.

    The figure benchmarks' entry point to store-backed regeneration
    (EXPERIMENTS.md §Heterogeneity): when ``store`` already holds the
    exact spec (hash hit, matching inputs digest), the cached entry is
    returned with ZERO device computation; otherwise the missing λ
    columns are filled via ``run_sweep_extend`` (which itself reuses any
    cached family columns) and the finished grid is persisted.  Either
    way the returned ``SweepResult`` is bitwise the stored entry.
    """
    if not isinstance(store, store_lib.SweepStore):
        store = store_lib.SweepStore(store)
    if store.has(spec):
        try:
            entry = store.get(spec, verify=True)
        except store_lib.StoreCorruptError as e:
            # corrupt cached entry: quarantine it and fall through to the
            # recompute path — transparent recovery, identical bytes
            store.quarantine(e.spec_hash, e.reason)
        else:
            in_digest = inputs_digest(sampler, w0, problem=problem,
                                      param_sets=param_sets,
                                      env_sets=env_sets,
                                      fleet_sets=fleet_sets)
            stored = entry.extra.get("inputs_digest")
            if stored is not None and stored != in_digest:
                raise ValueError(
                    f"store entry {entry.spec_hash} was computed from "
                    "different inputs (w0/sampler/env/fleet digests differ) "
                    "— same spec, different experiment; give this sweep its "
                    "own SweepSpec.tag")
            return arrays_to_result(entry)
    return run_sweep_extend(store, spec, sampler, w0, problem,
                            param_sets=param_sets, env_sets=env_sets,
                            fleet_sets=fleet_sets, mesh=mesh,
                            state_init_fn=state_init_fn,
                            store_dir=store_dir, extra=extra)
