"""Regression tests for the ISSUE 8 bugfix batch:

* ``kernels/gain.py`` ``env_blocks()`` — unknown block names raise with
  the valid set listed, and a non-integer value names the env var;
* ``experiments/runtime.py`` ``gc_finished`` — a crash between the
  summary-store commit and the lock removal leaves a stale INCOMPLETE
  lock on a provably finished sweep, which GC now reclaims (and ONLY
  then: a genuinely live or unverifiable lock still refuses);
* ``experiments/query.py`` — non-finite λ / comm budgets raise
  ``ValueError`` instead of silently clamping through ``np.interp``;
* ``experiments/serve_sweeps.py`` POST ``/query/batch`` — dict / null /
  scalar bodies and malformed item param types return 400, never 500.
"""

import json
import os
import threading
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.algorithm1 import ParamSampler
from repro.envs import GridWorld
from repro.experiments import SweepSpec, run_sweep
from repro.experiments import query as query_lib
from repro.experiments import serve_sweeps
from repro.experiments.query import TradeoffCurve
from repro.experiments.runtime import (
    gc_finished,
    run_sweep_resumable,
    store_result,
)
from repro.experiments.store import SweepStore
from repro.kernels.gain import env_blocks

try:  # py3.12 spells it differently; the server import is what matters
    from http.server import ThreadingHTTPServer
except ImportError:  # pragma: no cover
    from http.server import HTTPServer as ThreadingHTTPServer

EPS = 0.5
GW = GridWorld()
PROB = GW.vfa_problem(np.zeros(GW.num_states))
RHO = PROB.min_rho(EPS) * 1.0001
W0 = jnp.zeros(GW.num_states)


def _spec(**kw):
    base = dict(modes=("theoretical", "practical"), lambdas=(1e-3, 1e-1),
                seeds=(0, 1), rhos=(RHO,), eps=EPS, num_iterations=20,
                num_agents=2, trace="summary")
    base.update(kw)
    return SweepSpec(**base)


def _sampler():
    return ParamSampler(fn=GW.sampler_fn(10), params=GW.agent_params(W0, 2))


# ------------------------------------------------------- env_blocks -------


def test_env_blocks_parses_known_names(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BLOCKS",
                       "family_block_t=64, megastep_block_m=8")
    assert env_blocks() == {"family_block_t": 64, "megastep_block_m": 8}


def test_env_blocks_rejects_unknown_name(monkeypatch):
    """The original bug: a typo'd name parsed fine and did nothing."""
    monkeypatch.setenv("REPRO_KERNEL_BLOCKS", "megastep_blockm=64")
    with pytest.raises(ValueError, match="unknown block name") as e:
        env_blocks()
    # the message lists the valid names so the typo is self-serviceable
    assert "megastep_block_m" in str(e.value)
    assert "REPRO_KERNEL_BLOCKS" in str(e.value)


def test_env_blocks_bad_int_names_the_env_var(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BLOCKS", "family_block_t=sixty-four")
    with pytest.raises(ValueError, match="REPRO_KERNEL_BLOCKS") as e:
        env_blocks()
    assert "family_block_t" in str(e.value)
    assert "sixty-four" in str(e.value)


# ------------------------------------------------- gc stale lock ----------


def test_gc_reclaims_stale_lock_after_commit_unlock_crash(tmp_path):
    """Crash ordering: chunks durable -> summary committed -> (CRASH)
    -> lock never removed.  The sweep is finished; GC must reclaim."""
    spec = _spec(chunk_size=4)
    store = SweepStore(tmp_path / "store")
    chunks = str(tmp_path / "chunks")
    run_sweep_resumable(spec, _sampler(), W0, problem=PROB,
                        store_dir=chunks, summary_store=store)
    manifest = json.load(open(os.path.join(chunks, "manifest.json")))
    # re-create the lock exactly as run_sweep_resumable wrote it (its
    # content is the plan's exec hash) — the state a crash in the
    # commit-to-unlock window leaves behind
    with open(os.path.join(chunks, "INCOMPLETE"), "w") as f:
        f.write(manifest["exec_hash"])
    stats = gc_finished(chunks)
    assert stats["collected"] and stats["files"] > 0
    assert not os.path.exists(chunks)
    assert store.has(spec)          # the deliverable survives


def test_gc_still_refuses_stale_looking_lock_with_missing_chunk(tmp_path):
    """Matching lock hash but a missing chunk: NOT provably finished."""
    spec = _spec(chunk_size=4)
    store = SweepStore(tmp_path / "store")
    chunks = str(tmp_path / "chunks")
    run_sweep_resumable(spec, _sampler(), W0, problem=PROB,
                        store_dir=chunks, summary_store=store)
    manifest = json.load(open(os.path.join(chunks, "manifest.json")))
    with open(os.path.join(chunks, "INCOMPLETE"), "w") as f:
        f.write(manifest["exec_hash"])
    victim = sorted(f for f in os.listdir(chunks)
                    if f.startswith("chunk_"))[0]
    os.remove(os.path.join(chunks, victim))
    with pytest.raises(RuntimeError, match="INCOMPLETE"):
        gc_finished(chunks)


def test_gc_still_refuses_lock_without_committed_summary(tmp_path):
    """Matching lock + durable chunks but no summary-store record: the
    deliverable is not durable, so the lock is treated as live."""
    spec = _spec(chunk_size=4)
    chunks = str(tmp_path / "chunks")
    run_sweep_resumable(spec, _sampler(), W0, problem=PROB, store_dir=chunks)
    manifest = json.load(open(os.path.join(chunks, "manifest.json")))
    with open(os.path.join(chunks, "INCOMPLETE"), "w") as f:
        f.write(manifest["exec_hash"])
    with pytest.raises(RuntimeError, match="INCOMPLETE"):
        gc_finished(chunks)


# -------------------------------------------------- query validation ------


def _curve():
    return TradeoffCurve(
        mode="theoretical", rho=0.99,
        lambdas=np.array([1e-3, 1e-2, 1e-1]),
        comm=np.array([0.9, 0.5, 0.1]),
        j=np.array([0.1, 0.2, 0.3]), spec_hash="deadbeef")


@pytest.mark.parametrize("lam", [float("nan"), float("inf"),
                                 float("-inf"), 0.0, -1.0])
def test_tradeoff_at_rejects_nonfinite_and_nonpositive_lambda(lam):
    """The original bug: nan/-inf fed np.interp, which silently clamps
    to a grid edge and returns it as a valid answer."""
    with pytest.raises(ValueError, match="finite positive"):
        query_lib.tradeoff_at(_curve(), lam)


@pytest.mark.parametrize("budget", [float("nan"), float("inf"),
                                    float("-inf"), -0.1, 1.1])
def test_best_lambda_rejects_bad_budget(budget):
    with pytest.raises(ValueError, match="comm budget"):
        query_lib.best_lambda(_curve(), budget)


@pytest.mark.parametrize("budgets", [[0.5, float("nan")],
                                     [float("inf"), 0.5],
                                     [0.5, -0.1]])
def test_best_lambda_batch_rejects_bad_budget_vector(budgets):
    """The batch path's (b < 0) | (b > 1) check let NaN sail through."""
    with pytest.raises(ValueError, match="comm budget"):
        query_lib.best_lambda_batch(_curve(), budgets)


def test_best_lambda_batch_still_matches_scalar_path():
    curve = _curve()
    batch = query_lib.best_lambda_batch(curve, [0.2, 0.6, 1.0])
    for budget, got in zip([0.2, 0.6, 1.0], batch):
        assert got == query_lib.best_lambda(curve, budget)


# ------------------------------------------------ serve batch bodies ------


@pytest.fixture(scope="module")
def served():
    """One tiny real store entry behind a live HTTP handler."""
    import tempfile
    with tempfile.TemporaryDirectory() as root:
        store = SweepStore(os.path.join(root, "store"))
        spec = _spec(modes=("practical",), seeds=(0,), num_iterations=10)
        res = run_sweep(spec, _sampler(), W0, problem=PROB)
        store_result(store, spec, res)
        handler = serve_sweeps.make_handler(store, quiet=True)
        httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        try:
            yield f"http://127.0.0.1:{httpd.server_address[1]}"
        finally:
            httpd.shutdown()


def _post(base, data):
    req = urllib.request.Request(
        f"{base}/query/batch", data=data,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


@pytest.mark.parametrize("body", [b'{"not": "a batch"}', b"null", b"42",
                                  b'"queries"', b""])
def test_batch_rejects_non_batch_bodies_with_400(served, body):
    """dict / null / scalar / empty bodies: 400 with a message — the
    original bug 500'd the connection on the dict body's TypeError."""
    code, payload = _post(served, body)
    assert code == 400
    assert "error" in payload


def test_batch_malformed_item_params_fail_as_item_errors(served):
    """Bad param *types* inside items (lam=null, budget as object) fail
    that slot with an error body; the rest of the batch still answers."""
    body = json.dumps({"queries": [
        {"query": "tradeoff", "lam": None},
        {"query": "best_lambda", "budget": {"no": "sense"}},
        {"query": "curve"},
        "not-an-object",
    ]}).encode()
    code, payload = _post(served, body)
    assert code == 200
    results = payload["results"]
    assert len(results) == 4
    assert "error" in results[0]
    assert "error" in results[1]
    assert results[2]["query"] == "curve"       # healthy item unharmed
    assert "error" in results[3]
    assert payload["count"] == 4


def test_nonfinite_budget_400s_through_the_serve_path(served):
    """End to end: the query-layer finite check surfaces as HTTP 400."""
    for q in ("best_lambda?budget=nan", "best_lambda?budget=inf",
              "tradeoff?lam=nan", "tradeoff?lam=-1"):
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{served}/query/{q}")
        assert e.value.code == 400
