"""Durability analyzer (ISSUE 18): the replica-coherence classification
of scheduler state is machine-checked. The strict gate: the production
scheduler tree is analyzer-clean (every attribute classified, every
durable mutation KV-paired, every derived rebuild reachable from
recover(), budgets respected); the fixture pair exercises every rule
shape; the --json CLI reports per-rule finding counts and wall time."""

import json
import pathlib
import subprocess
import sys

import tomllib as _toml

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures" / "lint"
MANIFEST = REPO / "dev" / "analysis" / "durability.toml"

sys.path.insert(0, str(REPO))

from dev.analysis.core import analyze_file, run_paths  # noqa: E402


def _durability(path):
    return [f for f in analyze_file(str(path)) if f.rule == "durability"]


# -- the strict gate ---------------------------------------------------------

def test_scheduler_tree_is_durability_clean():
    """Acceptance: zero durability findings over the production scheduler
    package — every SchedulerState/server attribute is classified, every
    durable mutation pairs with a KV op, every derived rebuild is
    reachable from recover(), ephemeral counts are within budget, and the
    manifest agrees with the source annotations."""
    findings, _stats = run_paths(
        [str(REPO / "ballista_tpu" / "scheduler")], use_cache=False
    )
    dur = [f for f in findings if f.rule == "durability"]
    assert dur == [], "\n".join(f.format() for f in dur)


def test_manifest_covers_the_state_surface():
    """The reviewed classification table spans the full state surface:
    three owner classes, all three durability classes in use, and at
    least the ~20 attribute families the first sweep classified."""
    with open(MANIFEST, "rb") as f:
        man = _toml.load(f)
    owners = {(o["module"], o["class"]) for o in man["owners"]}
    assert owners == {
        ("scheduler.state", "SchedulerState"),
        ("scheduler.server", "SchedulerServer"),
        ("scheduler.server", "_PushSubscriber"),
    }
    attrs = man["attrs"]
    assert len(attrs) >= 20
    kinds = {row.split("(")[0] for row in attrs.values()}
    assert kinds == {"durable", "derived", "ephemeral"}
    # the attempt-guard policy names the two guards and carries reasons
    ag = man["attempt_guard"]
    assert set(ag["guards"]) == {"accept_task_status", "_spec_attempt_floor"}
    assert all(reason.strip() for reason in ag["reviewed"].values())


# -- fixture pair ------------------------------------------------------------

def test_durability_fixture_pair():
    """All three classes + the attempt-guard rule + the budgeted-ephemeral
    path: every bad shape fires, the canonical shapes are clean."""
    msgs = [f.message for f in _durability(FIXTURES / "durability_bad.py")]
    assert any("no `# durability:` annotation" in m for m in msgs), msgs
    assert any("needs a KV prefix token" in m for m in msgs)
    assert any("needs a reason" in m for m in msgs)
    assert any("needs the rebuild function's name" in m for m in msgs)
    assert any("conflicting durability classification" in m for m in msgs)
    assert any(
        "no KV operation against prefix 'assignments'" in m for m in msgs
    )
    assert any(
        "without consulting the attempt/ledger guard" in m for m in msgs
    )
    assert any("is NOT reachable from" in m for m in msgs)
    assert any("over its budget of 4" in m for m in msgs)
    assert any("dangling" in m for m in msgs)
    good = analyze_file(str(FIXTURES / "durability_good.py"))
    assert good == [], "\n".join(f.format() for f in good)


def test_attempt_guard_ok_annotation_is_load_bearing(tmp_path):
    """Stripping `# attempt-guard-ok:` from the good fixture's replay
    helper makes the attempt-guard finding appear — the annotation is
    what keeps it clean, not a hole in the rule."""
    src = (FIXTURES / "durability_good.py").read_text()
    needle = "    # attempt-guard-ok: replays a status the caller's guard " \
        "already vetted\n"
    assert needle in src
    p = tmp_path / "stripped.py"
    p.write_text(src.replace(needle, ""))
    msgs = [f.message for f in _durability(p)]
    assert any(
        "'replay_status' folds a TaskStatus" in m
        and "without consulting the attempt/ledger guard" in m
        for m in msgs
    ), msgs


# -- per-rule CLI stats (ISSUE 18 satellite) ---------------------------------

def test_json_reports_per_rule_finding_counts_and_wall_time():
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analysis",
         str(FIXTURES / "durability_bad.py"), "--no-cache", "--json"],
        cwd=str(REPO), capture_output=True, text=True,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    rules = out["stats"]["rules"]
    dur = rules["durability"]
    assert dur["findings"] >= 8
    assert dur["findings"] == sum(
        1 for f in out["findings"] if f["rule"] == "durability"
    )
    assert dur["wall_s"] >= 0
    # every per-file rule billed its wall time, findings or not
    for rule in ("lock-order", "readback-discipline", "tracer-hygiene"):
        assert rule in rules and rules[rule]["wall_s"] >= 0, rules
