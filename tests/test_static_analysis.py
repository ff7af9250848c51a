"""ballista-lint (dev/analysis): the analyzer itself is tier-1 — a clean
self-run over ballista_tpu/ gates the tree, each rule is exercised against
known-bad and known-good fixture snippets, and the suppression syntax
(mandatory reasons) plus per-file cache behavior are pinned."""

import ast
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures" / "lint"

sys.path.insert(0, str(REPO))

from dev.analysis.core import (  # noqa: E402
    RULE_NAMES,
    analyze_file,
    run_paths,
)

RULES = [
    "readback-discipline",
    "tracer-hygiene",
    "dtype-discipline",
    "guarded-by",
    "decline-discipline",
    "failure-discipline",
    "routing-discipline",
    "durability",
]


def _rules_hit(path) -> set:
    return {f.rule for f in analyze_file(str(path))}


# -- the gate: the production tree is clean ---------------------------------

def test_self_run_clean_over_package():
    findings, stats = run_paths([str(REPO / "ballista_tpu")], use_cache=False)
    assert findings == [], "\n".join(f.format() for f in findings)
    # ISSUE 3 acceptance: at most 5 reasoned suppressions in the package
    assert stats["suppressions"] <= 5
    assert stats["files"] > 50  # actually swept the tree


def test_all_rules_registered():
    names = RULE_NAMES()
    for r in RULES:
        assert r in names
    assert "lock-order" in names  # ISSUE 14
    assert "durability" in names  # ISSUE 18
    assert "lint-usage" in names


# -- who may know the device layer --------------------------------------------

def _imports(path: pathlib.Path) -> set:
    """Every module a file imports, at any depth, relative ones resolved."""
    pkg = list(path.relative_to(REPO).parts[:-1])
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            out.add(mod)
            out.update(f"{mod}.{a.name}" for a in node.names)
    return out


def test_client_utils_and_the_rpc_front_do_not_import_the_device_layer():
    pkg = REPO / "ballista_tpu"
    files = sorted([*(pkg / "client").glob("*.py"), *(pkg / "utils").glob("*.py"),
                    pkg / "scheduler" / "server.py", pkg / "scheduler" / "rpc.py"])
    assert len(files) > 8
    reach = {str(f.relative_to(REPO)): sorted(m for m in _imports(f)
                                              if (m + ".").startswith("ballista_tpu.ops."))
             for f in files}
    assert not any(reach.values()), {f: m for f, m in reach.items() if m}


def test_the_runtime_keeps_only_the_four_device_families():
    tree = ast.parse((REPO / "ballista_tpu" / "ops" / "runtime.py").read_text())
    stats = sorted(n.name for n in tree.body
                   if isinstance(n, ast.FunctionDef) and n.name.endswith("_stats"))
    assert stats == ["ingest_stats", "join_path_stats", "readback_stats", "routing_stats"]


# -- lock-order (ISSUE 14) ---------------------------------------------------

def test_lockorder_fixture_pair():
    """ISSUE 14: an undeclared nesting acquired in both orders (a cycle),
    a raw unwitnessable threading.Lock, a missing annotation, and a lying
    make_lock literal all fail lint; the canonical shapes (declared
    forward nesting, holds-lock helper, double-checked insert, annotated
    check-then-act) are clean."""
    findings = [
        f.message for f in analyze_file(str(FIXTURES / "lockorder_bad.py"))
        if f.rule == "lock-order"
    ]
    assert any("undeclared lock-order edge" in m for m in findings), findings
    assert any("potential deadlock: lock-order cycle" in m for m in findings)
    assert any("raw threading.Lock()" in m for m in findings)
    assert any("no guarded-by:/holds-lock: annotation" in m for m in findings)
    assert any("does not match its canonical identity" in m for m in findings)
    good = analyze_file(str(FIXTURES / "lockorder_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_atomicity_fixture_flagged():
    """ISSUE 14: a read-modify-write of guarded state spanning two
    acquisitions (check-then-act across a release) fails lint."""
    findings = [
        f.message for f in analyze_file(str(FIXTURES / "atomicity_bad.py"))
        if f.rule == "lock-order"
    ]
    assert any("check-then-act across a release" in m for m in findings)


# -- per-rule fixtures -------------------------------------------------------

@pytest.mark.parametrize("rule", RULES)
def test_bad_fixture_flags_its_rule(rule):
    stem = rule.split("-")[0]
    hit = _rules_hit(FIXTURES / f"{stem}_bad.py")
    assert rule in hit, f"{rule} did not fire on its bad fixture (hit: {hit})"


@pytest.mark.parametrize("rule", RULES)
def test_good_fixture_is_clean(rule):
    stem = rule.split("-")[0]
    findings = analyze_file(str(FIXTURES / f"{stem}_good.py"))
    assert findings == [], "\n".join(f.format() for f in findings)


def test_bad_fixtures_fail_via_cli():
    """Acceptance: `python -m dev.analysis` exits nonzero on each bad
    fixture (one CLI invocation per file, as CI would run it)."""
    for bad in sorted(FIXTURES.glob("*_bad.py")):
        proc = subprocess.run(
            [sys.executable, "-m", "dev.analysis", str(bad), "--no-cache"],
            cwd=str(REPO), capture_output=True, text=True,
        )
        assert proc.returncode == 1, (bad, proc.stdout, proc.stderr)


def test_tracer_rule_walks_call_graph():
    """The decoration site is `jax.jit(wrapped)`; the violation lives in a
    helper `wrapped` calls — the walk must reach it."""
    findings = analyze_file(str(FIXTURES / "tracer_bad.py"))
    assert any(
        f.rule == "tracer-hygiene" and "'helper'" in f.message for f in findings
    ), "\n".join(f.format() for f in findings)


def test_decline_rule_flags_all_three_shapes():
    findings = [
        f.message for f in analyze_file(str(FIXTURES / "decline_bad.py"))
        if f.rule == "decline-discipline"
    ]
    assert any("without a reason" in m for m in findings)
    assert any("ad-hoc" in m for m in findings)
    assert any("return None" in m for m in findings)


def test_overflow_decline_fixture_pair():
    """The M:N join tier-overflow decline site (ISSUE 4): a reasonless
    overflow raise / silent None is flagged; the canonical
    join_multiplicity_tier + step_aside + record_join_path shape is clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "decline_overflow_bad.py"))
        if f.rule == "decline-discipline"
    ]
    assert any("without a reason" in m for m in findings)
    assert any("return None" in m for m in findings)
    good = analyze_file(str(FIXTURES / "decline_overflow_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_failure_rule_flags_all_four_shapes():
    """ISSUE 5 satellite: anonymous fetch_failed, unregistered site,
    computed site, ad-hoc ChaosInjected raise."""
    findings = [
        f.message for f in analyze_file(str(FIXTURES / "failure_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any("lost location" in m for m in findings)
    assert any("unregistered chaos site" in m for m in findings)
    assert any("string literal" in m for m in findings)
    assert any("ad-hoc" in m and "ChaosInjected" in m for m in findings)


def test_failure_rule_scheduler_site_fixture_pair():
    """ISSUE 6 satellite: unregistered or computed (non-literal) chaos site
    names in SCHEDULER code fail lint; the registered-literal plan-write /
    crash shapes are clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "failure_sched_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any(
        "unregistered chaos site" in m and "scheduler.plan_commit" in m
        for m in findings
    ), findings
    assert any("string literal" in m for m in findings), findings
    good = analyze_file(str(FIXTURES / "failure_sched_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_failure_rule_tenancy_site_fixture_pair():
    """ISSUE 7 satellite: the new cache.put / scheduler.admit sites are
    registered — unregistered cache sites and computed admission site names
    in the tenancy code fail lint; the registered-literal shapes are clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "failure_tenancy_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any(
        "unregistered chaos site" in m and "cache.write" in m
        for m in findings
    ), findings
    assert any("string literal" in m for m in findings), findings
    good = analyze_file(str(FIXTURES / "failure_tenancy_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_failure_rule_push_site_fixture_pair():
    """ISSUE 8 satellite: the new scheduler.push / aot.load sites are
    registered — an unregistered push-stream site and a computed AOT-load
    site name in latency-tier code fail lint; the registered-literal shapes
    are clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "failure_push_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any(
        "unregistered chaos site" in m and "scheduler.stream" in m
        for m in findings
    ), findings
    assert any("string literal" in m for m in findings), findings
    good = analyze_file(str(FIXTURES / "failure_push_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_failure_rule_speculation_fixture_pair():
    """ISSUE 11 satellite: speculation discipline — a minted duplicate
    attempt (`.speculative = True`) with no same-scope durable ledger
    record (_spec_put / _ledger_put) fails lint, as does the unregistered
    straggler chaos site; the ledgered mint, the ledgered promotion, the
    non-literal echo site, and the registered `task.slow` literal are
    clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "failure_spec_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any("ad-hoc speculative attempt" in m for m in findings), findings
    assert any(
        "unregistered chaos site" in m and "task.straggle" in m
        for m in findings
    ), findings
    good = analyze_file(str(FIXTURES / "failure_spec_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_failure_rule_batch_site_fixture_pair():
    """ISSUE 13 satellite: the new scheduler.batch site is registered — an
    unregistered grouping site and a computed site name in batching code
    fail lint; the registered-literal shape (generation-rotated sequence
    key) is clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "failure_batch_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any(
        "unregistered chaos site" in m and "scheduler.group" in m
        for m in findings
    ), findings
    assert any("string literal" in m for m in findings), findings
    good = analyze_file(str(FIXTURES / "failure_batch_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_failure_rule_fleet_site_fixture_pair():
    """ISSUE 15: the new shuffle.store and fleet.scale sites are
    registered — an unregistered storage site and a computed fleet site
    name fail lint; the registered-literal shapes (plan-coordinate keys on
    the storage seams, evaluation-sequence key on the scale decision) are
    clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "failure_fleet_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any(
        "unregistered chaos site" in m and "shuffle.publish" in m
        for m in findings
    ), findings
    assert any("string literal" in m for m in findings), findings
    good = analyze_file(str(FIXTURES / "failure_fleet_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_failure_rule_exchange_site_fixture_pair():
    """ISSUE 16: the exchange.evict site is registered — an unregistered
    exchange site and a computed exchange site name fail lint; the
    registered-literal shape (plan-coordinate + consuming-attempt key on
    the residency probe) is clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "failure_exchange_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any(
        "unregistered chaos site" in m and "exchange.drop" in m
        for m in findings
    ), findings
    assert any("string literal" in m for m in findings), findings
    good = analyze_file(str(FIXTURES / "failure_exchange_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_failure_rule_delta_site_fixture_pair():
    """ISSUE 19: the cache.advance site is registered — an unregistered
    advancement site and a computed cache site name fail lint; the
    registered-literal shape (result-key-keyed verdict BEFORE any KV
    write of the advanced entry) is clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "failure_delta_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any(
        "unregistered chaos site" in m and "cache.fold" in m
        for m in findings
    ), findings
    assert any("string literal" in m for m in findings), findings
    good = analyze_file(str(FIXTURES / "failure_delta_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_failure_rule_replica_site_fixture_pair():
    """ISSUE 20: the scheduler.lease and kv.lease sites are registered —
    an unregistered renewal site and a computed lease site name fail lint;
    the registered-literal shapes (generation/round-keyed verdicts BEFORE
    any lease write) are clean."""
    findings = [
        f.message
        for f in analyze_file(str(FIXTURES / "failure_replica_bad.py"))
        if f.rule == "failure-discipline"
    ]
    assert any(
        "unregistered chaos site" in m and "scheduler.renew" in m
        for m in findings
    ), findings
    assert any("string literal" in m for m in findings), findings
    good = analyze_file(str(FIXTURES / "failure_replica_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_routing_rule_fixture_pair():
    """ISSUE 10 satellite: a decline-helper call with no routing
    observation in scope and no cold-path annotation fails lint — a
    FOREIGN .observe() method included (only the qualified
    costmodel.observe counts); the recorder-paired and annotated shapes
    are clean, covering each accepted recorder (record_routing /
    record_routing_event / record_join_path / costmodel.observe)."""
    findings = [
        f for f in analyze_file(str(FIXTURES / "routing_bad.py"))
        if f.rule == "routing-discipline"
    ]
    assert len(findings) == 3, "\n".join(f.format() for f in findings)
    assert {f.line for f in findings} == {10, 14, 19}
    good = analyze_file(str(FIXTURES / "routing_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_routing_rule_skips_helper_definitions():
    """The canonical helpers in ops/kernels.py ARE the decline channel;
    their own bodies must not be flagged (and the production kernels module
    stays clean under the rule)."""
    findings = [
        f for f in analyze_file(str(REPO / "ballista_tpu" / "ops" / "kernels.py"))
        if f.rule == "routing-discipline"
    ]
    assert findings == [], "\n".join(f.format() for f in findings)


def test_failure_rule_sites_track_chaos_registry():
    """The rule reads SITES from ballista_tpu/utils/chaos.py, so the two
    can't drift silently."""
    from ballista_tpu.utils import chaos
    from dev.analysis.rules_failure import _registered_sites

    assert _registered_sites(str(REPO / "ballista_tpu" / "executor" /
                                 "execution_loop.py")) == frozenset(chaos.SITES)


def test_guarded_rule_checks_holds_lock_callers():
    findings = [
        f.message for f in analyze_file(str(FIXTURES / "guarded_bad.py"))
        if f.rule == "guarded-by"
    ]
    assert any("requires holding" in m for m in findings)
    assert any("accessed outside" in m for m in findings)


# -- suppressions ------------------------------------------------------------

def test_suppression_with_reason_suppresses():
    findings = analyze_file(str(FIXTURES / "suppress_ok.py"))
    assert findings == [], "\n".join(f.format() for f in findings)


def test_suppression_without_reason_rejected():
    findings = analyze_file(str(FIXTURES / "suppress_noreason.py"))
    rules = {f.rule for f in findings}
    assert "lint-usage" in rules  # the reasonless directive is itself flagged
    assert "readback-discipline" in rules  # and it did NOT suppress


def test_unused_suppression_flagged(tmp_path):
    p = tmp_path / "unused.py"
    p.write_text(
        "# ballista-lint: path=ballista_tpu/ops/fixture_unused.py\n"
        "x = 1  # ballista-lint: disable=readback-discipline -- nothing here\n"
    )
    findings = analyze_file(str(p))
    assert any(
        f.rule == "lint-usage" and "unused suppression" in f.message
        for f in findings
    )


def test_unknown_rule_in_suppression_flagged(tmp_path):
    p = tmp_path / "unknown.py"
    p.write_text("x = 1  # ballista-lint: disable=no-such-rule -- why\n")
    findings = analyze_file(str(p))
    assert any(
        f.rule == "lint-usage" and "unknown rule" in f.message for f in findings
    )


# -- CLI / cache / json ------------------------------------------------------

def test_json_output_and_cache_roundtrip(tmp_path):
    work = tmp_path / "pkg" / "ballista_tpu" / "ops"
    work.mkdir(parents=True)
    shutil.copy(FIXTURES / "readback_bad.py", work / "mod.py")
    cache = tmp_path / "cache.json"

    def run():
        proc = subprocess.run(
            [sys.executable, "-m", "dev.analysis", str(work), "--json",
             "--cache-file", str(cache)],
            cwd=str(REPO), capture_output=True, text=True,
        )
        return proc.returncode, json.loads(proc.stdout)

    rc1, out1 = run()
    assert rc1 == 1 and not out1["ok"]
    assert out1["stats"]["cache_hits"] == 0
    assert {f["rule"] for f in out1["findings"]} == {"readback-discipline"}
    assert all(
        {"rule", "path", "line", "col", "message"} <= set(f) for f in out1["findings"]
    )

    rc2, out2 = run()  # warm: same findings, served from cache
    assert rc2 == 1
    assert out2["stats"]["cache_hits"] == out2["stats"]["files"] == 1
    assert out2["findings"] == out1["findings"]

    # an edit invalidates the entry and flips the verdict
    text = (work / "mod.py").read_text().replace(
        "return np.asarray(out)  # unrecorded d2h transfer",
        "from ballista_tpu.ops.runtime import record_readback\n"
        "    arr = np.asarray(out)\n"
        "    record_readback(arr.shape[-1], arr.nbytes)\n"
        "    return arr",
    ).replace(
        "return np.asarray(run(cols, aux))  # unrecorded d2h transfer",
        "from ballista_tpu.ops.runtime import readback\n"
        "    return readback(run(cols, aux))",
    )
    (work / "mod.py").write_text(text)
    os.utime(work / "mod.py")
    rc3, out3 = run()
    assert rc3 == 0 and out3["ok"], out3["findings"]


def test_manifest_edit_invalidates_per_file_cache(tmp_path):
    """ISSUE 18 satellite: per-file verdicts depend on the durability
    manifest (owner coverage, [attrs] agreement), so the per-file cache
    key must incorporate the manifests' content hash — including
    env-overridden manifests the blob-level analyzer hash never sees.
    Pre-fix, run 2 served the stale 'clean' verdict from run 1's cache."""
    work = tmp_path / "pkg"
    work.mkdir()
    (work / "mod.py").write_text(
        "# ballista-lint: path=ballista_tpu/scheduler/mod.py\n"
        "class Thing:\n"
        "    def __init__(self):\n"
        "        self.a = 1\n"
    )
    cache = tmp_path / "cache.json"
    manifest = tmp_path / "durability.toml"
    env = dict(os.environ, BALLISTA_DURABILITY_MANIFEST=str(manifest))

    def run():
        proc = subprocess.run(
            [sys.executable, "-m", "dev.analysis", str(work), "--json",
             "--cache-file", str(cache)],
            cwd=str(REPO), capture_output=True, text=True, env=env,
        )
        return proc.returncode, json.loads(proc.stdout)

    # manifest v1: Thing is nobody's owner -> the unannotated attr is fine
    manifest.write_text("[attrs]\n")
    rc1, out1 = run()
    assert rc1 == 0 and out1["ok"], out1["findings"]
    assert out1["stats"]["cache_hits"] == 0

    # manifest v2 makes Thing an owner: the SAME file (same mtime/size)
    # must be re-analyzed and flag the missing annotation
    manifest.write_text(
        "[[owners]]\n"
        'module = "scheduler.mod"\n'
        'class = "Thing"\n'
        "[attrs]\n"
    )
    rc2, out2 = run()
    assert rc2 == 1 and not out2["ok"], out2
    assert out2["stats"]["cache_hits"] == 0  # stale entry NOT served
    assert any(
        f["rule"] == "durability"
        and "no `# durability:` annotation" in f["message"]
        for f in out2["findings"]
    ), out2["findings"]

    # unchanged manifest: the refreshed verdict is served from cache
    rc3, out3 = run()
    assert rc3 == 1
    assert out3["stats"]["cache_hits"] == out3["stats"]["files"] == 1
    assert out3["findings"] == out2["findings"]


def test_suppression_budget_enforced(tmp_path):
    p = tmp_path / "budget.py"
    lines = ["# ballista-lint: path=ballista_tpu/ops/fixture_budget.py"]
    for i in range(6):
        lines.append(f"x{i} = {i}  # ballista-lint: disable=lint-usage -- r{i}")
    p.write_text("\n".join(lines) + "\n")
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analysis", str(p), "--no-cache", "--json"],
        cwd=str(REPO), capture_output=True, text=True,
    )
    out = json.loads(proc.stdout)
    assert out["over_suppression_budget"] and proc.returncode == 1
