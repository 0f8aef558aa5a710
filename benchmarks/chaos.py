"""Chaos suite: the fault matrix, asserted end to end (ISSUE 10).

Every durability fault site gets a cell per applicable kind: a CHILD
process runs a real garnet sweep through the resumable runtime with
``REPRO_FAULTS`` injecting the fault (crashes are hard ``os._exit(43)``
deaths — no ``finally`` blocks, no writer-queue drain, exactly like a
kill), then a clean RECOVERY child re-runs and the parent asserts the
recovered summary-store entry is **bitwise identical** (content digest)
to a clean uninterrupted run's, with corrupt files quarantined rather
than silently merged.  Torn/flip cells pair the mangle with a later
crash (``site:torn:1,site:crash_after:2``) so the resume path actually
*reads* the corrupt chunk instead of the in-memory copy.

The children run with ``JAX_PLATFORMS=cpu`` (rows say ``platform:
cpu``): they measure durability, not a device, and the parent process may
already hold the chip.  Serving cells run in-process: a federation of store entries is poisoned
one hash at a time (bit flip, vanished entry dir, injected transient
I/O) and the rows assert the poisoned hash answers a structured 503
with a per-hash reason while every healthy hash keeps serving 200 — and
that the ``QueryServiceClient`` retry policy absorbs dropped
connections (``serve.request`` faults) without masking real failures
(retries and response errors are separate counters).

Row kinds: ``chaos`` (one per durability cell: site, kind, crashed,
recovered_bitwise, quarantined count, recovery_s) and ``chaos_serving``
(one per serving cell).  ``benchmarks.check_bench`` gates the committed
``experiments/bench/chaos.json``: every expected site must have a row,
every ``recovered_bitwise``/``healthy_kept_serving`` flag must be True,
every ``recovery_s`` finite and positive.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from benchmarks.common import EXP_DIR  # noqa: F401  (bench-suite convention)
from repro import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EPS = 0.4
RHO = 0.999
CHILD_PLATFORM = "cpu"

# site -> applicable kinds.  Kinds with no surface at a site (torn at a
# lock transition — nothing is mangle-able there) are exercised where
# the surface exists; crash kinds run everywhere.
DURABILITY_CELLS = (
    # (site, kind, REPRO_FAULTS spec, child mode, expect_crash)
    ("ckpt.write", "crash_before", "ckpt.write:crash_before:2", "sweep"),
    ("ckpt.write", "crash_after", "ckpt.write:crash_after:2", "sweep"),
    ("ckpt.write", "torn",
     "ckpt.write:torn:1,ckpt.write:crash_after:2", "sweep"),
    ("ckpt.write", "flip",
     "ckpt.write:flip:1,ckpt.write:crash_after:2", "sweep"),
    ("ckpt.rename", "crash_before", "ckpt.rename:crash_before:2", "sweep"),
    ("ckpt.rename", "crash_after", "ckpt.rename:crash_after:2", "sweep"),
    ("ckpt.fsync", "crash_before", "ckpt.fsync:crash_before:2", "durable"),
    ("ckpt.fsync", "crash_after", "ckpt.fsync:crash_after:2", "durable"),
    ("store.commit", "crash_before", "store.commit:crash_before:1", "sweep"),
    ("store.commit", "crash_after", "store.commit:crash_after:1", "sweep"),
    ("store.commit", "torn", "store.commit:torn:1", "sweep"),
    ("store.commit", "flip", "store.commit:flip:1", "sweep"),
    ("store.merge", "crash_before", "store.merge:crash_before:1", "extend"),
    ("runtime.lock", "crash_after", "runtime.lock:crash_after:1", "sweep"),
    ("runtime.unlock", "crash_before",
     "runtime.unlock:crash_before:1", "sweep"),
    ("runtime.gc", "crash_before", "runtime.gc:crash_before:1", "gc"),
)

SMOKE_CELLS = ("ckpt.write:crash_after", "ckpt.write:torn",
               "store.commit:torn", "store.commit:crash_after",
               "runtime.unlock:crash_before")


def _scale(smoke: bool) -> dict:
    if smoke:
        return dict(envs=4, states=8, agents=2, iters=12, samples=6,
                    lam_base=(1e-3, 1e-1), lam_ext=(1e-2,), chunk=2)
    return dict(envs=8, states=12, agents=2, iters=40, samples=8,
                lam_base=(1e-4, 1e-3, 1e-1), lam_ext=(1e-2,), chunk=4)


# --------------------------------------------------------------- child -----
# One real garnet sweep through the resumable runtime.  Runs in a
# subprocess so injected crashes (os._exit(43)) die like a kill; the
# parent only ever reads the store/chunk directories the child leaves.


def _child_setup(cfg: dict, lambdas: tuple):
    import jax.numpy as jnp
    from repro.core.algorithm1 import ParamSampler
    from repro.envs import (family_sampler_fn, garnet_env_family,
                            garnet_fleet_sets)
    from repro.experiments import SweepSpec

    envs, fam = garnet_env_family(cfg["envs"], num_states=cfg["states"])
    w0 = jnp.zeros(cfg["states"])
    sampler = ParamSampler(fn=family_sampler_fn(cfg["samples"]), params=None)
    fleets = garnet_fleet_sets(envs, w0, cfg["agents"], num_junk=0)
    spec = SweepSpec(
        modes=("theoretical", "practical"), lambdas=tuple(lambdas),
        seeds=(0,), rhos=(RHO,), eps=EPS, num_iterations=cfg["iters"],
        num_agents=cfg["agents"], trace="summary", chunk_size=cfg["chunk"],
        tag="chaos")
    return spec, sampler, w0, fam, fleets


def child_main(mode: str, root: str, smoke: bool) -> None:
    cfg = _scale(smoke)
    chunks = os.path.join(root, "chunks")
    store_root = os.path.join(root, "store")
    if mode == "gc":
        from repro.experiments.runtime import gc_finished
        gc_finished(chunks, store_root)
        return
    lambdas = (tuple(cfg["lam_base"]) + tuple(cfg["lam_ext"])
               if mode == "extend" else cfg["lam_base"])
    spec, sampler, w0, fam, fleets = _child_setup(cfg, lambdas)
    if mode == "extend":
        # store-first extension: reuses the base-λ entry the parent seeded,
        # computes only lam_ext, merges (the store.merge site), persists
        from repro.experiments import sweep_or_load
        sweep_or_load(store_root, spec, sampler, w0, env_sets=fam,
                      fleet_sets=fleets,
                      store_dir=os.path.join(root, "chunks_ext"))
    else:
        from repro.experiments.runtime import run_sweep_resumable
        run_sweep_resumable(spec, sampler, w0, env_sets=fam,
                            fleet_sets=fleets, store_dir=chunks,
                            summary_store=store_root,
                            durable=(mode == "durable"))


# -------------------------------------------------------------- parent -----


def _spawn(mode: str, root: str, smoke: bool,
           fault_spec: str = "") -> tuple[int, float, str]:
    # JAX_PLATFORMS=cpu: the children measure durability, not a device,
    # and the parent (benchmarks.run runs every suite in one process) may
    # already hold the chip, which a child could not then reach
    env = dict(os.environ, JAX_PLATFORMS=CHILD_PLATFORM,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop(faults.ENV_VAR, None)
    if fault_spec:
        env[faults.ENV_VAR] = fault_spec
    cmd = [sys.executable, "-m", "benchmarks.chaos", "--child", mode,
           "--root", root]
    if smoke:
        cmd.append("--smoke")
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=1200)
    wall = time.perf_counter() - t0
    return proc.returncode, wall, (proc.stdout + proc.stderr)[-2000:]


def _entry_digest(store_root: str, spec_hash: str) -> str:
    from repro.experiments.store import SweepStore, arrays_digest
    entry = SweepStore(store_root).get(spec_hash, verify=True)
    return arrays_digest(entry.arrays)


def _only_hash(store_root: str) -> str:
    from repro.experiments.store import SweepStore
    hashes = SweepStore(store_root).hashes()
    if not hashes:
        raise RuntimeError(f"{store_root} holds no committed entry")
    return hashes[0]


def _count_quarantined(root: str) -> int:
    n = 0
    for _, dirs, files in os.walk(root):
        n += sum(".quarantined" in name for name in dirs + files)
    return n


def _full_spec_hash(smoke: bool, extended: bool) -> str:
    cfg = _scale(smoke)
    lambdas = (tuple(cfg["lam_base"]) + tuple(cfg["lam_ext"]) if extended
               else cfg["lam_base"])
    spec, _, _, _, _ = _child_setup(cfg, lambdas)
    from repro.experiments.store import spec_hash
    return spec_hash(spec)


def _durability_rows(smoke: bool, work: str) -> list[dict]:
    rows = []
    cells = [c for c in DURABILITY_CELLS
             if not smoke or f"{c[0]}:{c[1]}" in SMOKE_CELLS]

    # one clean reference run, shared by every sweep-mode cell
    clean_root = os.path.join(work, "clean")
    rc, clean_s, out = _spawn("sweep", clean_root, smoke)
    if rc != 0:
        raise RuntimeError(f"clean reference run failed (rc={rc}): {out}")
    base_hash = _only_hash(os.path.join(clean_root, "store"))
    ref_digest = _entry_digest(os.path.join(clean_root, "store"), base_hash)

    # clean reference for the extension path (base grid, then extend)
    ext_hash = ref_ext_digest = None
    if any(c[3] == "extend" for c in cells):
        ext_clean = os.path.join(work, "clean_ext")
        for phase in ("sweep", "extend"):
            rc, _, out = _spawn(phase, ext_clean, smoke)
            if rc != 0:
                raise RuntimeError(
                    f"clean {phase} reference failed (rc={rc}): {out}")
        ext_hash = _full_spec_hash(smoke, extended=True)
        ref_ext_digest = _entry_digest(os.path.join(ext_clean, "store"),
                                       ext_hash)

    for site, kind, fault_spec, mode in cells:
        root = os.path.join(work, f"{site}.{kind}".replace(":", "_"))
        # seed the pre-fault state the cell needs
        if mode == "extend":
            rc, _, out = _spawn("sweep", root, smoke)
            if rc != 0:
                raise RuntimeError(f"extend seed failed: {out}")
        child = {"durable": "durable", "extend": "extend",
                 "gc": "sweep"}.get(mode, "sweep")
        if mode == "gc":
            rc, _, out = _spawn("sweep", root, smoke)   # a finished sweep
            if rc != 0:
                raise RuntimeError(f"gc seed failed: {out}")
            child = "gc"

        expect_crash = "crash" in fault_spec
        faulted_rc, _, out = _spawn(child, root, smoke, fault_spec=fault_spec)
        crashed = faulted_rc == faults.CRASH_EXIT
        if expect_crash and not crashed:
            raise RuntimeError(
                f"{site}:{kind}: child exited rc={faulted_rc}, expected "
                f"injected crash rc={faults.CRASH_EXIT}\n{out}")
        if not expect_crash and faulted_rc != 0:
            raise RuntimeError(f"{site}:{kind}: faulted child failed "
                               f"(rc={faulted_rc}): {out}")

        # recovery: a clean re-run of the same child mode
        rc, recovery_s, out = _spawn(child, root, smoke)
        if rc != 0:
            raise RuntimeError(f"{site}:{kind}: recovery run failed "
                               f"(rc={rc}): {out}")

        want_hash = ext_hash if mode == "extend" else base_hash
        want_digest = ref_ext_digest if mode == "extend" else ref_digest
        got = _entry_digest(os.path.join(root, "store"), want_hash)
        if got != want_digest:
            raise RuntimeError(
                f"{site}:{kind}: recovered entry digest {got} != clean "
                f"{want_digest} — recovery is NOT bitwise identical")
        if mode == "gc":
            left = [n for n in os.listdir(os.path.join(root, "chunks"))
                    if n.startswith("chunk_")] if os.path.isdir(
                        os.path.join(root, "chunks")) else []
            if left:
                raise RuntimeError(f"gc recovery left chunks: {left}")
        rows.append(dict(
            bench="chaos", site=site, kind=kind, child=child,
            platform=CHILD_PLATFORM,
            faults=fault_spec, crashed=crashed, faulted_rc=faulted_rc,
            recovered_bitwise=True,
            quarantined=_count_quarantined(root),
            recovery_s=float(recovery_s), clean_s=float(clean_s),
            overhead_pct=round(100.0 * (recovery_s / clean_s - 1.0), 1),
            us_per_call=recovery_s * 1e6))
    return rows


# ------------------------------------------------------- serving cells -----


def _serving_rows(clean_store: str, smoke: bool) -> list[dict]:
    from http.server import ThreadingHTTPServer

    from repro.experiments.client import (QueryServiceClient, RetryPolicy)
    from repro.experiments.serve_sweeps import make_handler
    from repro.experiments.store import SweepStore

    work = tempfile.mkdtemp(prefix="chaos_serving_")
    root = os.path.join(work, "store")
    shutil.copytree(clean_store, root)
    s = SweepStore(root)
    h1 = s.hashes()[0]
    base = s.get(h1)
    victims = []
    for tag in ("chaos-b", "chaos-c", "chaos-d"):
        spec = dict(base.spec)
        spec["tag"] = tag
        victims.append(s.put(spec, base.arrays, base.axes, extra=base.extra))
    h2, h3, h4 = victims

    handler = make_handler(root, quiet=True)
    registry = handler.registry
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    rows = []
    try:
        client = QueryServiceClient("127.0.0.1", httpd.server_address[1],
                                    policy=RetryPolicy(retries=4, seed=7))

        def healthy() -> bool:
            st, _ = client.get("best_lambda", budget=0.2, hash=h1)
            return st == 200

        def row(site, kind, t0, **kw):
            rows.append(dict(bench="chaos_serving", site=site, kind=kind,
                             healthy_kept_serving=healthy(),
                             us_per_call=(time.perf_counter() - t0) * 1e6,
                             **kw))

        # bit-flipped entry: structured 503 for that hash, others serve
        t0 = time.perf_counter()
        assert healthy()
        faults.flip_bit(os.path.join(root, h2, "arrays.npz"))
        st, body = client.get("curve", hash=h2)
        row("registry.load", "flip", t0, poisoned_status=st,
            structured=bool(body.get("unavailable"))
            and body.get("spec_hash") == h2)

        # entry dir deleted after registration: 503 + stale-table eviction
        t0 = time.perf_counter()
        st, _ = client.get("curve", hash=h3)
        assert st == 200
        cached_before = registry.cached_tables()
        shutil.rmtree(os.path.join(root, h3))
        st, body = client.get("curve", hash=h3)
        row("registry.load", "vanish", t0, poisoned_status=st,
            structured=bool(body.get("unavailable")),
            evicted=registry.cached_tables() < cached_before)

        # transient I/O during a cold load: one 503, then recovers
        t0 = time.perf_counter()
        faults.install("registry.load:oserror:1")
        st1, body1 = client.get("curve", hash=h4)
        st2, _ = client.get("curve", hash=h4)
        faults.reset()
        row("registry.load", "oserror", t0, poisoned_status=st1,
            structured=bool(body1.get("unavailable")), recovered=st2 == 200)

        # dropped connection mid-request: the client's bounded
        # backoff+jitter retry recovers it transparently
        t0 = time.perf_counter()
        faults.install("serve.request:oserror:1")
        before = client.stats["transient_retries"]
        st, _ = client.get("best_lambda", budget=0.2, hash=h1)
        faults.reset()
        row("serve.request", "oserror", t0, poisoned_status=st,
            recovered=st == 200,
            transient_retries=client.stats["transient_retries"] - before)

        # injected latency: slow but correct
        t0 = time.perf_counter()
        faults.install("serve.request:latency:1")
        st, _ = client.get("best_lambda", budget=0.2, hash=h1)
        faults.reset()
        row("serve.request", "latency", t0, poisoned_status=st,
            recovered=st == 200)

        client.close()
    finally:
        faults.reset()
        httpd.shutdown()
        shutil.rmtree(work, ignore_errors=True)
    for r in rows:
        if not r["healthy_kept_serving"]:
            raise RuntimeError(f"healthy hash stopped serving during "
                               f"{r['site']}:{r['kind']}")
    return rows


def run(smoke: bool = False) -> list[dict]:
    work = tempfile.mkdtemp(prefix="chaos_bench_")
    try:
        rows = _durability_rows(smoke, work)
        rows += _serving_rows(os.path.join(work, "clean", "store"), smoke)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return rows


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", default=None,
                    choices=("sweep", "durable", "extend", "gc"))
    ap.add_argument("--root", default=None)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        child_main(args.child, args.root, args.smoke)
        return
    for row in run(smoke=args.smoke):
        print(json.dumps(row))


if __name__ == "__main__":
    main()
