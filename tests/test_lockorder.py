"""Concurrency analyzer (ISSUE 14): static lock-order graph, deadlock
detection, manifest enforcement, atomicity check, the dynamic lock
witness, the witness-vs-static cross-check, and --jobs parallel analysis.

The production gate itself — `python -m dev.analysis` clean with the
lock-order rule enabled — lives in test_static_analysis.py; this file
exercises the machinery."""

import json
import pathlib
import subprocess
import sys
import textwrap
import threading

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures" / "lint"
sys.path.insert(0, str(REPO))

from dev.analysis import lockgraph  # noqa: E402
from dev.analysis.core import analyze_file, run_paths  # noqa: E402
from dev.analysis.lockgraph import (  # noqa: E402
    EdgeSite,
    LockGraph,
    Manifest,
    diff_witness,
)
from dev.analysis.rules_lockorder import RULE, build_graph, static_edges  # noqa: E402
from ballista_tpu.utils import locks  # noqa: E402
from ballista_tpu.utils import tracing


def _site(src, dst, line=1, func="f", via=""):
    return EdgeSite(src, dst, "x.py", line, func, via)


def _graph_of(facts_src: dict):
    """build_graph over {display_path: module_source} inline sources."""
    from dev.analysis.core import SourceFile
    from dev.analysis.rules_lockorder import extract_facts

    facts = {}
    for path, src in facts_src.items():
        sf = SourceFile(path, textwrap.dedent(src), path)
        facts[path] = extract_facts(sf)
    return build_graph(facts)


# -- graph construction units ------------------------------------------------

def test_direct_nesting_edge():
    graph, _ = _graph_of({"ballista_tpu/ops/m.py": """
        from ballista_tpu.utils.locks import make_lock
        _a_lock = make_lock("ops.m._a_lock")
        _b_lock = make_lock("ops.m._b_lock")
        def f():
            with _a_lock:
                with _b_lock:
                    pass
    """})
    assert ("ops.m._a_lock", "ops.m._b_lock") in graph.edge_set()
    site = graph.site("ops.m._a_lock", "ops.m._b_lock")
    assert site.func == "f" and site.via == ""


def test_same_module_call_chain_edge():
    graph, _ = _graph_of({"ballista_tpu/ops/m.py": """
        from ballista_tpu.utils.locks import make_lock
        _a_lock = make_lock("ops.m._a_lock")
        _b_lock = make_lock("ops.m._b_lock")
        def helper():
            with _b_lock:
                pass
        def f():
            with _a_lock:
                helper()
    """})
    assert ("ops.m._a_lock", "ops.m._b_lock") in graph.edge_set()
    assert graph.site("ops.m._a_lock", "ops.m._b_lock").via == "helper()"


def test_holds_lock_entry_context_edge():
    graph, _ = _graph_of({"ballista_tpu/ops/m.py": """
        from ballista_tpu.utils.locks import make_lock
        _a_lock = make_lock("ops.m._a_lock")
        _b_lock = make_lock("ops.m._b_lock")
        # holds-lock: _a_lock
        def locked_helper():
            with _b_lock:
                pass
    """})
    assert ("ops.m._a_lock", "ops.m._b_lock") in graph.edge_set()


def test_cross_module_call_resolved_by_base_segment():
    graph, _ = _graph_of({
        "ballista_tpu/scheduler/st.py": """
            from ballista_tpu.utils.locks import make_lock
            def f(self):
                with self.kv.lock():
                    self.kv.put("k", b"v")
        """,
        "ballista_tpu/scheduler/kv.py": """
            from ballista_tpu.utils.locks import make_rlock
            class B:
                def __init__(self):
                    self._mu = make_rlock("scheduler.kv.lock")
                def put(self, k, v):
                    with self._mu:
                        pass
        """,
    })
    # kv.lock -> kv.lock is reentrant self-re-entry, NOT an edge
    assert ("scheduler.kv.lock", "scheduler.kv.lock") not in graph.edge_set()


def test_cross_module_unique_bare_name_resolution():
    graph, _ = _graph_of({
        "ballista_tpu/ops/a.py": """
            from ballista_tpu.utils.locks import make_lock
            _a_lock = make_lock("ops.a._a_lock")
            def f():
                with _a_lock:
                    record_thing(1)
        """,
        "ballista_tpu/ops/b.py": """
            from ballista_tpu.utils.locks import make_lock
            _b_lock = make_lock("ops.b._b_lock")
            def record_thing(n):
                with _b_lock:
                    pass
        """,
    })
    assert ("ops.a._a_lock", "ops.b._b_lock") in graph.edge_set()


def test_foreign_attribute_calls_do_not_resolve():
    """`self._cache.get(...)` under a lock must NOT paint an edge to some
    other module's lock-acquiring `get` (the phantom-kv.get regression)."""
    graph, _ = _graph_of({
        "ballista_tpu/ops/a.py": """
            from ballista_tpu.utils.locks import make_lock
            _a_lock = make_lock("ops.a._a_lock")
            class C:
                def f(self):
                    with _a_lock:
                        self._cache.get("k")
        """,
        "ballista_tpu/scheduler/kv.py": """
            from ballista_tpu.utils.locks import make_rlock
            class B:
                def __init__(self):
                    self._mu = make_rlock("scheduler.kv.lock")
                def get(self, k):
                    with self._mu:
                        pass
        """,
    })
    assert ("ops.a._a_lock", "scheduler.kv.lock") not in graph.edge_set()


def test_may_acquire_annotation_seeds_edges():
    graph, _ = _graph_of({"ballista_tpu/ops/m.py": """
        from ballista_tpu.utils.locks import make_lock
        _a_lock = make_lock("ops.m._a_lock")
        # may-acquire: ops.stage._prepare_lock
        def dynamic_dispatch(plan):
            plan.execute()
        def f(plan):
            with _a_lock:
                dynamic_dispatch(plan)
    """})
    assert ("ops.m._a_lock", "ops.stage._prepare_lock") in graph.edge_set()


# -- cycle detection ---------------------------------------------------------

def test_two_cycle_detected_with_both_paths():
    g = LockGraph()
    g.add(_site("a", "b", 1, "f"))
    g.add(_site("b", "a", 9, "g"))
    cycles = g.cycles()
    assert ["a", "b", "a"] in cycles
    report = g.cycle_report(["a", "b", "a"])
    assert "x.py:1 in f" in report and "x.py:9 in g" in report


def test_three_cycle_detected_once():
    g = LockGraph()
    for s, d in (("a", "b"), ("b", "c"), ("c", "a")):
        g.add(_site(s, d))
    cycles = g.cycles()
    assert len(cycles) == 1 and set(cycles[0]) == {"a", "b", "c"}


def test_dag_has_no_cycles():
    g = LockGraph()
    for s, d in (("a", "b"), ("a", "c"), ("b", "c")):
        g.add(_site(s, d))
    assert g.cycles() == []


# -- manifest ----------------------------------------------------------------

def test_manifest_roundtrip_of_real_file():
    m = Manifest.load()
    assert m.rank["scheduler.kv.lock"] == 0  # the outermost lock
    assert m.reentrant("scheduler.kv.lock")
    assert m.plan_tree("physical.join._build_lock")
    # dst_group expands: the join build lock reaches the stage substrate
    assert ("physical.join._build_lock", "ops.stage._prepare_lock") in m.declared
    # a declared edge with a reason
    assert m.declared[("scheduler.kv.lock", "scheduler.server._push_mu")]


def test_manifest_check_edge_semantics():
    m = Manifest({
        "order": ["a", "b"],
        "edges": [{"src": "a", "dst": "b", "reason": "r"}],
        "locks": {
            "r1": {"reentrant": True},
            "t1": {"instance_tree": "tree"},
            "p1": {"plan_tree": "plan"},
            "p2": {"plan_tree": "plan"},
        },
    })
    assert m.check_edge("a", "b") is None  # declared + forward
    assert "undeclared" in m.check_edge("b", "a")
    assert "undeclared" in m.check_edge("a", "c")
    assert m.check_edge("r1", "r1") is None  # reentrant self
    assert m.check_edge("t1", "t1") is None  # instance-tree self
    assert "self-deadlock" in m.check_edge("a", "a")
    assert m.check_edge("p1", "p2") is None  # plan-tree pair exempt
    m2 = Manifest({"order": ["b"], "edges": [{"src": "a", "dst": "b"}]})
    assert "missing from the canonical `order`" in m2.check_edge("a", "b")


def test_manifest_inversion_detected():
    m = Manifest({
        "order": ["a", "b"],
        "edges": [{"src": "b", "dst": "a", "reason": "declared backwards"}],
    })
    assert "inversion" in m.check_edge("b", "a")


# -- the production tree's graph --------------------------------------------

def test_production_graph_contains_known_edges_and_no_cycles():
    edges = static_edges([str(REPO / "ballista_tpu")])
    for e in (
        ("scheduler.kv.lock", "scheduler.state._tenant_mu"),
        ("scheduler.kv.lock", "scheduler.server._push_mu"),
        ("scheduler.kv.lock", "scheduler.server._status_mu"),
        ("scheduler.kv.lock", "ops.costmodel._lock"),
        ("ops.stage._prepare_lock", "ops.runtime._res_lock"),
        ("ops.kernels._stage_cache_lock", "ops.runtime._res_lock"),
    ):
        assert e in edges, f"expected production edge {e} missing"
    m = Manifest.load()
    # every production edge declared + forward; no cycles (ex plan pairs)
    g = LockGraph()
    for s, d in edges:
        if not m.plan_pair(s, d):
            g.add(_site(s, d))
            assert m.check_edge(s, d) is None, (s, d, m.check_edge(s, d))
    assert g.cycles() == []


# -- atomicity ---------------------------------------------------------------

def test_atomicity_fixture_flagged():
    findings = [
        f for f in analyze_file(str(FIXTURES / "atomicity_bad.py"))
        if f.rule == RULE
    ]
    assert len(findings) == 1
    assert "check-then-act across a release" in findings[0].message


def test_atomicity_good_patterns_clean():
    """Double-checked insert, kill-on-fresh-reassignment, and the
    atomicity-ok annotation are all clean (lockorder_good.py)."""
    assert analyze_file(str(FIXTURES / "lockorder_good.py")) == []


def test_atomicity_ok_annotation_required(tmp_path):
    """Removing the annotation from the good fixture's reviewed
    check-then-act makes it a finding (the annotation is load-bearing)."""
    src = (FIXTURES / "lockorder_good.py").read_text().replace(
        "    # atomicity-ok: best-effort estimate; last writer wins by design\n",
        "",
    )
    p = tmp_path / "stripped.py"
    p.write_text(src.replace("path=ballista_tpu/ops/lockorder_good.py",
                             "path=ballista_tpu/ops/lockorder_good.py"))
    findings = [f for f in analyze_file(str(p)) if f.rule == RULE]
    assert any("check-then-act" in f.message for f in findings)


# -- dynamic witness ---------------------------------------------------------

@pytest.fixture
def witness():
    locks.reset_witness()
    locks.enable_witness()
    yield locks
    locks.disable_witness()
    locks.reset_witness()


def test_witness_records_edges(witness):
    a = locks.make_lock("scheduler.kv.lock")
    b = locks.make_lock("scheduler.server._push_mu")
    with a:
        with b:
            pass
    assert witness.witness_edges() == {
        ("scheduler.kv.lock", "scheduler.server._push_mu"): 1
    }
    assert witness.witness_violations() == []


def test_witness_asserts_on_declared_order_inversion(witness):
    a = locks.make_lock("scheduler.kv.lock")  # rank 0
    b = locks.make_lock("scheduler.server._push_mu")  # rank 1
    with pytest.raises(locks.LockOrderViolation) as ei:
        with b:
            with a:
                pass
    msg = str(ei.value)
    assert "inversion" in msg
    # both stacks attached, as the ISSUE demands
    assert "acquired at:" in msg and msg.count("File ") >= 2
    assert any(
        v["kind"] == "order_inversion" for v in witness.witness_violations()
    )


def test_witness_asserts_same_object_self_deadlock(witness):
    a = locks.make_lock("ops.runtime._res_lock")
    with pytest.raises(locks.LockOrderViolation, match="deadlocks now"):
        with a:
            with a:
                pass


def test_witness_allows_rlock_reentry_and_plan_tree_nesting(witness):
    r = locks.make_rlock("scheduler.kv.lock")
    with r:
        with r:
            pass
    j1 = locks.make_lock("physical.join._build_lock")
    j2 = locks.make_lock("physical.join._build_lock")
    with j1:
        with j2:  # distinct instances of a plan-tree class: legal
            pass
    assert not witness.witness_violations()


def test_witness_threads_have_independent_stacks(witness):
    a = locks.make_lock("scheduler.kv.lock")
    b = locks.make_lock("scheduler.server._push_mu")
    errs = []

    def other():
        try:
            with b:  # bare acquisition in another thread: no edge
                pass
        except Exception as e:  # pragma: no cover
            errs.append(e)

    with a:
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert not errs
    assert ("scheduler.kv.lock", "scheduler.server._push_mu") \
        not in witness.witness_edges()


def test_witness_dump_and_replay(witness, tmp_path):
    a = locks.make_lock("scheduler.kv.lock")
    b = locks.make_lock("scheduler.server._push_mu")
    with a:
        with b:
            pass
    out = tmp_path / "witness.json"
    rec = witness.dump(str(out))
    loaded = lockgraph.load_witness(str(out))
    assert loaded == json.loads(json.dumps(rec))
    assert loaded["edges"][0]["src"] == "scheduler.kv.lock"
    assert loaded["edges"][0]["count"] == 1
    assert "held_stack" in loaded["edges"][0]


def test_witness_disabled_is_transparent():
    locks.reset_witness()
    assert not locks.witness_enabled()
    a = locks.make_lock("ops.runtime._res_lock")
    with a:
        with a if False else locks.make_lock("utils.tracing._mu"):
            pass
    assert locks.witness_edges() == {}
    assert a.acquire(blocking=False)
    a.release()


# -- witness-vs-static diff --------------------------------------------------

def test_diff_witness_missed_and_stale():
    manifest = Manifest({
        "order": ["a", "b", "c"],
        "edges": [
            {"src": "a", "dst": "b", "reason": "live"},
            {"src": "a", "dst": "c", "reason": "stale declaration"},
        ],
    })
    witness = {
        "edges": [
            {"src": "a", "dst": "b", "count": 3},
            {"src": "b", "dst": "c", "count": 1},  # analyzer missed this
        ],
        "violations": [],
    }
    report = diff_witness(witness, {("a", "b")}, manifest)
    assert report["missed"] == [("b", "c")]
    assert ("a", "c") in report["never_witnessed"]
    assert ("a", "b") not in report["never_witnessed"]


def test_diff_witness_plan_pairs_exempt_from_missed():
    manifest = Manifest({
        "order": [],
        "locks": {
            "p1": {"plan_tree": "x"},
            "p2": {"plan_tree": "x"},
        },
    })
    witness = {"edges": [{"src": "p1", "dst": "p2", "count": 1}],
               "violations": []}
    assert diff_witness(witness, set(), manifest)["missed"] == []


def test_check_witness_cli(tmp_path):
    """--check-witness: a runtime edge the static analyzer missed exits 1;
    a witness that is a subset of the static graph exits 0."""
    bogus = tmp_path / "bogus.json"
    bogus.write_text(json.dumps({
        "edges": [{"src": "utils.tracing._mu", "dst": "scheduler.kv.lock",
                   "count": 1}],
        "violations": [],
    }))
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analysis", "--check-witness", str(bogus),
         "ballista_tpu"],
        cwd=str(REPO), capture_output=True, text=True,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "MISSED statically" in proc.stdout

    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps({
        "edges": [{"src": "scheduler.kv.lock",
                   "dst": "scheduler.state._tenant_mu", "count": 5}],
        "violations": [],
    }))
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analysis", "--check-witness", str(ok),
         "ballista_tpu", "--json"],
        cwd=str(REPO), capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["ok"] and out["missed"] == []


def test_check_witness_cli_fails_on_recorded_violation(tmp_path):
    w = tmp_path / "v.json"
    w.write_text(json.dumps({
        "edges": [],
        "violations": [{"kind": "order_inversion", "src": "a", "dst": "b"}],
    }))
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analysis", "--check-witness", str(w),
         "ballista_tpu"],
        cwd=str(REPO), capture_output=True, text=True,
    )
    assert proc.returncode == 1
    assert "RUNTIME VIOLATION" in proc.stdout


def test_check_witness_cli_merges_multiple_dumps(tmp_path):
    """ISSUE 18 satellite: witness CI lanes fork worker processes that
    each dump <OUT>.<pid>; --check-witness accepts the flag repeatedly
    and merges the edge sets before the diff — a missed edge in ANY dump
    fails, duplicate edges collapse to one merged runtime edge."""
    known = {"src": "scheduler.kv.lock",
             "dst": "scheduler.state._tenant_mu", "count": 2}
    a = tmp_path / "w.json.101"
    a.write_text(json.dumps({"edges": [known], "violations": []}))
    b = tmp_path / "w.json.102"
    b.write_text(json.dumps({
        "edges": [dict(known, count=3),
                  {"src": "utils.tracing._mu", "dst": "scheduler.kv.lock",
                   "count": 1}],
        "violations": [],
    }))
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analysis",
         "--check-witness", str(a), "--check-witness", str(b),
         "ballista_tpu", "--json"],
        cwd=str(REPO), capture_output=True, text=True,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["witness_files"] == 2
    assert out["missed"] == [["utils.tracing._mu", "scheduler.kv.lock"]]
    # the duplicated known edge merged into ONE runtime edge
    assert out["runtime_edges"] == 2

    # both dumps subsets of the static graph: the merged check passes
    b.write_text(json.dumps({"edges": [dict(known, count=3)],
                             "violations": []}))
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analysis",
         "--check-witness", str(a), "--check-witness", str(b),
         "ballista_tpu", "--json"],
        cwd=str(REPO), capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["ok"] and out["runtime_edges"] == 1


def test_env_armed_witness_dump_is_pid_suffixed(tmp_path):
    """Env-armed processes dump to <OUT>.<pid>, never <OUT> itself —
    concurrent workers inheriting one BALLISTA_LOCK_WITNESS_OUT must not
    clobber each other's atexit os.replace."""
    import os

    out = tmp_path / "w.json"
    code = (
        "from ballista_tpu.utils import locks\n"
        "a = locks.make_lock('scheduler.kv.lock')\n"
        "b = locks.make_lock('scheduler.state._tenant_mu')\n"
        "with a:\n"
        "    with b:\n"
        "        pass\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=str(REPO), capture_output=True, text=True,
        env=dict(os.environ, BALLISTA_LOCK_WITNESS="1",
                 BALLISTA_LOCK_WITNESS_OUT=str(out),
                 PYTHONPATH=str(REPO)),
    )
    assert proc.returncode == 0, proc.stderr
    assert not out.exists()
    dumps = list(tmp_path.glob("w.json.*"))
    assert len(dumps) == 1, dumps
    rec = lockgraph.load_witness(str(dumps[0]))
    assert {(e["src"], e["dst"]) for e in rec["edges"]} == {
        ("scheduler.kv.lock", "scheduler.state._tenant_mu")
    }


# -- parallel analysis (--jobs) ---------------------------------------------

def test_jobs_parallel_matches_serial_and_caches(tmp_path):
    work = tmp_path / "pkg" / "ballista_tpu" / "ops"
    work.mkdir(parents=True)
    import shutil

    for name in ("lockorder_bad.py", "atomicity_bad.py", "readback_bad.py",
                 "lockorder_good.py"):
        shutil.copy(FIXTURES / name, work / name)
    c1, c2 = tmp_path / "c1.json", tmp_path / "c2.json"
    serial, s_stats = run_paths([str(work)], cache_path=str(c1), jobs=1)
    parallel, p_stats = run_paths([str(work)], cache_path=str(c2), jobs=3)
    assert [f.to_dict() for f in serial] == [f.to_dict() for f in parallel]
    assert s_stats["files"] == p_stats["files"] == 4
    assert p_stats["cache_hits"] == 0
    # warm second parallel run: per-file results all served from cache,
    # global lock-order findings recomputed identically
    warm, w_stats = run_paths([str(work)], cache_path=str(c2), jobs=3)
    assert w_stats["cache_hits"] == 4
    assert [f.to_dict() for f in warm] == [f.to_dict() for f in parallel]


def test_jobs_cli_flag(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analysis", "ballista_tpu/utils",
         "--jobs", "2", "--no-cache"],
        cwd=str(REPO), capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- witness e2e smoke (the CI gate's in-suite twin) -------------------------

def test_witness_chaos_e2e_zero_violations_zero_missed(tmp_path):
    """ISSUE 14 acceptance: one seeded chaos e2e — executor death mid-run
    plus a scheduler restart on the same store — under
    ballista.debug.lock_witness=1. Hard asserts: ZERO declared-order
    violations recorded at runtime, and --check-witness semantics hold
    (zero runtime edges the static analyzer missed)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    import ballista_tpu.scheduler.state as state_mod
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.executor.runtime import StandaloneCluster
    from ballista_tpu.utils.chaos import ChaosInjector

    # deterministic death seed (same scan as test_chaos.py: pure hashing)
    def find_death_seed():
        for seed in range(2000):
            inj = ChaosInjector(seed, rate=0.005, sites={"executor.death"})

            def death_poll(eid, horizon):
                for n in range(1, horizon):
                    if inj.should_inject("executor.death", f"{eid}/poll{n}"):
                        return n
                return None

            d0 = death_poll("local-0", 17)
            if d0 is not None and 4 <= d0 and death_poll("local-1", 400) is None:
                return seed
        pytest.fail("no death seed found")

    rng = np.random.default_rng(7)
    n = 5000
    t = pa.table({
        "g": pa.array([f"k{v}" for v in rng.integers(0, 5, n)]),
        "v": pa.array(rng.integers(0, 100, n), type=pa.int64()),
    })
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path)

    import time

    locks.reset_witness()
    locks.enable_witness()
    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    tracing.counters("recovery", reset=True)
    cluster = StandaloneCluster(n_executors=2, config=BallistaConfig({
        "ballista.debug.lock_witness": "1",
        "ballista.chaos.rate": "0.005",
        "ballista.chaos.seed": str(find_death_seed()),
        "ballista.chaos.sites": "executor.death",
        "ballista.rpc.retries": "20",
    }))
    cluster.scheduler_impl.lost_task_check_interval = 0.3
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings={
            "ballista.cache.results": "false",
        })
        ctx.register_parquet("t", path)
        sql = "select g, sum(v) as s, count(*) as c from t group by g order by g"
        first = ctx.sql(sql).collect()
        # let the seeded death fire (local-0 dies within its first ~16
        # polls at 250ms), then restart the scheduler on the same store
        # (ISSUE 6 path) and re-run on the degraded cluster
        deadline = time.time() + 10
        while time.time() < deadline and not tracing.counters("recovery").get(
            "chaos_executor_death"
        ):
            time.sleep(0.1)
        cluster.restart_scheduler()
        second = ctx.sql(sql).collect()
        assert first.to_pydict() == second.to_pydict()
        ctx.close()
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
        cluster.shutdown()
        locks.disable_witness()

    stats = tracing.counters("recovery", reset=True)
    assert stats.get("chaos_executor_death", 0) >= 1, stats
    assert stats.get("scheduler_restart", 0) >= 1, stats
    violations = locks.witness_violations()
    assert violations == [], violations
    out = tmp_path / "witness.json"
    witness_rec = locks.dump(str(out))
    locks.reset_witness()
    assert witness_rec["edges"], "witness saw no edges — not armed?"
    edges = static_edges([str(REPO / "ballista_tpu")])
    report = diff_witness(witness_rec, edges, Manifest.load())
    assert report["missed"] == [], (
        "runtime edges the static analyzer missed: "
        f"{report['missed']}\n(add the call-resolution or a may-acquire "
        "annotation; the witness caught an analyzer gap)"
    )


def test_witness_rlock_reentry_under_intermediate_lock(witness):
    """Review regression: re-entering an already-held REENTRANT lock after
    acquiring an intermediate lock (kv.lock -> counter lock -> kv.get, the
    canonical scheduler shape) can never block — it must not record a
    backwards edge or raise, whatever the declared ranks say."""
    kv = locks.make_rlock("scheduler.kv.lock")  # rank 0
    counter = locks.make_lock("ops.costmodel._lock")  # ranked far below
    with kv:
        with counter:
            with kv:  # legal re-entry, not an inversion
                pass
    assert witness.witness_violations() == []
    assert ("ops.costmodel._lock", "scheduler.kv.lock") \
        not in witness.witness_edges()


def test_static_rlock_reentry_under_intermediate_lock():
    """The static mirror of the same review regression: a nested re-entry
    of a held reentrant lock (direct `with`, or via a callee like kv.get)
    must not derive edges from the intermediate locks."""
    graph, _ = _graph_of({"ballista_tpu/scheduler/m.py": """
        from ballista_tpu.utils.locks import make_lock, make_rlock
        _kv_mu = make_rlock("scheduler.m._kv_mu")
        _c_lock = make_lock("scheduler.m._c_lock")
        def reenter_direct(self):
            with _kv_mu:
                with _c_lock:
                    with _kv_mu:
                        pass
        def kv_get(self):
            with _kv_mu:
                pass
        def reenter_via_call(self):
            with _kv_mu:
                with _c_lock:
                    kv_get(self)
    """})
    assert ("scheduler.m._c_lock", "scheduler.m._kv_mu") \
        not in graph.edge_set()
    assert ("scheduler.m._kv_mu", "scheduler.m._c_lock") in graph.edge_set()
