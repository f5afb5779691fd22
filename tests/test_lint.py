"""tpulint self-check: the analyzer runs over ray_tpu/ itself and must
report nothing beyond the checked-in baseline.

This is the CI gate the ISSUE asks for: any NEW static hazard (blocking
get in an actor, dropped ref, lock-order inversion, jit impurity,
unbounded poll, swallowed conn error) fails tier-1 until it is fixed or
explicitly accepted via --update-baseline. Runs from any cwd: paths are
anchored at the repo root so fingerprints match the baseline.
"""

import json
import os
import re
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu.lint import baseline as bl
from ray_tpu.lint.cli import main as lint_main
from ray_tpu.lint.engine import lint_paths

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
PKG = os.path.join(ROOT, "ray_tpu")


@pytest.fixture(scope="module")
def tree_findings():
    """The whole tree linted ONCE, with the default catalog (TPL, CCR and ERR together:
    ``lint/rules/__init__.py``): every self-check below reads this one result, a catalog's
    check its own rules' findings out of it."""
    return lint_paths([PKG], root=ROOT)


def test_self_check_no_new_findings(tree_findings):
    d = bl.diff(tree_findings, bl.load(bl.default_baseline_path()))
    assert d.new == [], (
        "tpulint found NEW hazards (fix them, or accept deliberate ones "
        "with `python -m ray_tpu.lint ray_tpu/ --update-baseline`):\n"
        + "\n".join(f.render() for f in d.new)
    )


def test_self_check_baseline_not_stale(tree_findings):
    d = bl.diff(tree_findings, bl.load(bl.default_baseline_path()))
    assert d.stale == [], (
        "baseline entries no longer reproduce (a finding was fixed): "
        "re-run --update-baseline to shrink the baseline:\n"
        + "\n".join(str(e) for e in d.stale)
    )


def _tracked_files():
    """What git would commit; in a checkout that is not a repository, what is there."""
    r = subprocess.run(["git", "ls-files"], cwd=ROOT, capture_output=True, text=True)
    if r.returncode == 0 and r.stdout.strip():
        return r.stdout.split("\n")
    return [os.path.relpath(os.path.join(d, n), ROOT) for d, _, names in os.walk(ROOT) for n in names]


def test_readme_names_only_files_that_exist():
    """README.md describes the system as it is: every file it names, in
    backticks or in a fenced block, is in the tree (`core/runtime.py`
    for `ray_tpu/core/runtime.py`: matched by suffix)."""
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        text = f.read()
    fence = re.compile(r"```.*?```", re.S)
    spans = fence.findall(text) + re.findall(r"`([^`\n]+)`", fence.sub("", text))
    tracked = ["/" + p for p in _tracked_files()]
    missing = set()
    for word in " ".join(spans).split():
        word = re.sub(r"(::.*|:\d[\d,-]*)$", "", word.rstrip(",:;)"))  # a test's name, a line number
        if not word.endswith((".py", ".json", ".md")) or re.search(r"[*<>{}$\"'(]", word):
            continue  # not a file's name, or one with a wildcard or a placeholder
        if not any(p.endswith("/" + word.lstrip("./")) for p in tracked):
            missing.add(word)
    assert not missing, f"README.md names files that are not in the tree: {sorted(missing)}"


CORE = os.path.join(PKG, "core")  # the CLI-behavior tests scope to one
# subtree (where the checked-in baseline's entries live): their contracts
# are path-independent and a full-tree walk per assertion is tier-1 time
# the self-check tests above already spend once


def test_cli_exit_codes(tmp_path):
    # clean tree against the real baseline -> 0 (subset coverage: entries
    # outside ray_tpu/core are simply not consulted)
    assert lint_main([CORE, "--root", ROOT]) == 0
    # same tree with an empty baseline -> 1 iff any findings exist at all
    empty = tmp_path / "empty.json"
    empty.write_text('{"version": 1, "tool": "tpulint", "entries": {}}')
    findings = lint_paths([CORE], root=ROOT)
    expected = 1 if findings else 0
    assert lint_main([CORE, "--root", ROOT, "--baseline", str(empty)]) == expected


def test_cli_update_baseline_roundtrip(tmp_path):
    out = tmp_path / "bl.json"
    assert lint_main([CORE, "--root", ROOT, "--baseline", str(out), "--update-baseline"]) == 0
    doc = json.loads(out.read_text())
    assert doc["tool"] == "tpulint" and isinstance(doc["entries"], dict)
    # a freshly-written baseline always yields a clean run
    assert lint_main([CORE, "--root", ROOT, "--baseline", str(out)]) == 0


def test_cli_select_restricts_rules():
    # TPL005-only run over the jax ops tree is clean (its jit bodies are pure)
    assert lint_main([os.path.join(PKG, "ops"), "--root", ROOT, "--select", "TPL005", "--no-baseline"]) == 0
    assert lint_main([PKG, "--select", "NOPE"]) == 2


def test_cli_stale_baseline_fails_the_gate(tmp_path):
    # an accepted entry that no longer reproduces (here: a fabricated one
    # inside the linted tree) must fail, or its unused budget would
    # silently absorb a reintroduced finding
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({
        "version": 1, "tool": "tpulint",
        "entries": {
            "deadbeefdeadbeef": {
                "rule": "TPL006", "path": "ray_tpu/ops/layers.py",
                "context": "nope", "message": "never existed", "count": 1,
            },
        },
    }))
    assert lint_main([os.path.join(PKG, "ops"), "--root", ROOT, "--baseline", str(stale)]) == 1


def test_cli_subset_runs_have_no_phantom_staleness(tmp_path):
    # the real baseline's node_agent TPL006 entries are OUTSIDE ray_tpu/ops
    # (and outside --select TPL001): neither run may call them stale
    assert lint_main([os.path.join(PKG, "ops"), "--root", ROOT]) == 0
    assert lint_main([CORE, "--root", ROOT, "--select", "TPL001"]) == 0


def test_cli_update_baseline_merges_outside_coverage(tmp_path):
    out = tmp_path / "bl.json"
    # two-subtree accept first (core holds the baseline's entries)
    assert lint_main([CORE, os.path.join(PKG, "ops"), "--root", ROOT, "--baseline", str(out), "--update-baseline"]) == 0
    before = json.loads(out.read_text())["entries"]
    assert before, "fixture needs accepted entries outside ray_tpu/ops"
    # subset re-accept must keep entries for files outside ray_tpu/ops
    assert lint_main([os.path.join(PKG, "ops"), "--root", ROOT, "--baseline", str(out), "--update-baseline"]) == 0
    after = json.loads(out.read_text())["entries"]
    assert after == before, "subset --update-baseline dropped out-of-coverage entries"
    # and the merged file still yields a clean run over both subtrees
    assert lint_main([CORE, os.path.join(PKG, "ops"), "--root", ROOT, "--baseline", str(out)]) == 0


def test_cli_overlapping_paths_lint_each_file_once():
    # a tree plus a file inside it must not double-lint the file: the
    # duplicates would overflow the baseline's accepted counts
    overlap = [CORE, os.path.join(PKG, "core", "node_agent.py")]
    assert lint_main(overlap + ["--root", ROOT]) == 0
    findings = lint_paths(overlap, root=ROOT)
    assert findings == lint_paths([CORE], root=ROOT)


def test_cli_nonexistent_path_is_a_usage_error(tmp_path):
    # a typo'd path must not produce a silently-green zero-file run
    assert lint_main([str(tmp_path / "no_such_tree"), "--root", ROOT]) == 2
    with pytest.raises(FileNotFoundError):
        lint_paths([str(tmp_path / "no_such_tree")], root=ROOT)


def test_module_entrypoint_and_rt_wiring():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.lint", "--list-rules"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=120,
    )
    assert r.returncode == 0 and "TPL001" in r.stdout and "TPL007" in r.stdout
    r2 = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "lint", "ray_tpu", "--root", ROOT],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=300,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr


# ============================================================ concur gate
def test_ccr_self_check_clean_modulo_baseline(tree_findings):
    """The concurrency-discipline pass over ray_tpu/ itself: every
    blocking-under-lock / hot-path-sync hazard is either fixed or a
    baseline entry with a hand-written why (the deliberate ones: the
    controller reconcile loop, drain idempotency). Any NEW CCR finding
    fails tier-1 — including any regression of the admission-path prefix
    fetch, whose item-3a debt entries were RETIRED when the fetch moved
    off the engine lock (the async fetch worker)."""
    from ray_tpu.lint.concur import concur_rule_ids

    findings = [f for f in tree_findings if f.rule in concur_rule_ids()]
    ccr_ids = concur_rule_ids() | {"TPL004"}
    entries = {fp: e for fp, e in bl.load(bl.default_baseline_path()).items()
               if e["rule"] in ccr_ids}
    d = bl.diff(findings, entries)
    assert d.new == [], (
        "NEW concurrency hazards in ray_tpu/ (fix, inline-disable with a "
        "rationale, or accept with --update-baseline + a why):\n"
        + "\n".join(f.render() for f in d.new)
    )
    assert d.stale == [], d.stale
    # the deliberate hazards stay TRACKED, not invisible
    assert d.suppressed >= 7


def test_ccr_baseline_holds_no_stale_roadmap_debt(tree_findings):
    """A baseline entry citing a ROADMAP item as accepted DEBT must stop
    existing once the code stops tripping the rule — debt entries that
    outlive their hazard would silently mask a regression reintroducing
    it. Item 3a (the synchronous admission-path fetch) is the precedent:
    its two CCR001 entries were deleted when the fetch moved to the
    async worker, and the engine's admission path must now run CCR-clean
    with NO engine-path fetch entry in the ledger at all."""
    entries = bl.load(bl.default_baseline_path())
    debt = [e for e in entries.values()
            if "accepted debt" in e.get("why", "") or "ROADMAP item" in e.get("why", "")]
    assert debt == [], (
        "baseline still carries roadmap-debt entries; retire them with the "
        f"fix that clears the hazard: {debt}"
    )
    # and specifically: no baseline entry suppresses anything on the
    # engine's admission/fetch path anymore
    assert not any(e["path"].endswith("llm/engine.py") for e in entries.values())
    # the stale-drop path proves the remaining ledger is live: a full
    # concur pass uses every entry it keeps (bl.diff flags unused budget)
    from ray_tpu.lint.concur import concur_rule_ids

    findings = [f for f in tree_findings if f.rule in concur_rule_ids()]
    ccr_ids = concur_rule_ids() | {"TPL004"}
    ccr_entries = {fp: e for fp, e in entries.items() if e["rule"] in ccr_ids}
    d = bl.diff(findings, ccr_entries)
    assert d.stale == [], (
        f"stale baseline entries (accepted hazards the code no longer trips): {d.stale}"
    )


def test_cli_select_ccr001_runs_only_that_rule(tmp_path, capsys):
    # one file with a CCR001 shape AND a TPL002 shape: --select=CCR001
    # must report only the former, and the JSONL rule id must carry the
    # catalog-correct id (satellite: select/list-rules span all catalogs)
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time\n\n"
        "class Pump:\n"
        "    def tick(self, actor):\n"
        "        actor.ping.remote()\n"
        "        with self._lock:\n"
        "            time.sleep(0.5)\n"
    )
    assert lint_main([str(bad), "--root", str(tmp_path), "--no-baseline",
                      "--select", "CCR001", "--format=json"]) == 1
    docs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert docs and {d["rule"] for d in docs} == {"CCR001"}
    # without the select, the same file trips both catalogs
    assert lint_main([str(bad), "--root", str(tmp_path), "--no-baseline",
                      "--format=json"]) == 1
    docs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert {"CCR001", "TPL002"} <= {d["rule"] for d in docs}


def test_cli_concur_flag_scopes_to_ccr_catalog(tmp_path, capsys):
    # --concur over the subtree that holds the baseline's entries runs clean against the committed baseline
    assert lint_main([CORE, "--root", ROOT, "--concur"]) == 0
    # and it implies the CCR selection: a TPL002 drop is NOT reported
    bad = tmp_path / "bad.py"
    bad.write_text("def kick(actor):\n    actor.ping.remote()\n")
    assert lint_main([str(bad), "--root", str(tmp_path), "--no-baseline", "--concur"]) == 0


# ============================================================ jaxcheck gate
def test_jaxcheck_self_check_runs_clean():
    """The jaxpr-level pass over every registered entry point must be
    clean: every deliberate exception is an inline per-arg disable with a
    rationale (see model_runner.fused_step's tokens lane) or a baseline
    entry. Any new JXC finding fails tier-1 until fixed or accepted."""
    from ray_tpu.lint.jaxcheck import run_jaxcheck

    findings = run_jaxcheck(root=ROOT)
    d = bl.diff(findings, bl.load(bl.default_baseline_path()))
    assert d.new == [], (
        "jaxcheck found NEW jaxpr-level hazards:\n" + "\n".join(f.render() for f in d.new)
    )


def test_jaxcheck_traces_at_least_thirty_entries():
    from ray_tpu.lint.jaxcheck import import_entry_modules, registry

    import_entry_modules()
    entries = registry.all_entries()
    # PR 4 registered 8; the speculative subsystem (llm/spec/) added 4;
    # disaggregated serving (llm/disagg/scatter.py) adds its extract +
    # scatter-in pairs; the int8 KV cache registers quantized variants of
    # every hot-path program it touches (fused decode x2, spec verify x2,
    # disagg extract x2 + scatter x2); tensor-parallel serving adds the
    # shard_map'd fused/paged-fused/spec-verify steps over mesh buckets
    # (where JXC005 finally audits real serving-path collectives); the
    # cluster KV plane (llm/kvplane/quant.py) adds the wire
    # quantize/dequantize pair on the publish/remote-hit paths; the
    # Pallas paged-attention kernel (llm/pallas/paged_attn.py) adds its
    # fp + int8 entries over interpret-mode buckets — any entry silently
    # dropping out of the registry is an invariant check that stopped
    # running
    assert len(entries) >= 32, [e.name for e in entries]
    subsystems = {e.name.split(".")[0] for e in entries}
    assert {"llm", "parallel", "collective"} <= subsystems
    names = {e.name for e in entries}
    assert {"llm.spec_verify", "llm.spec_verify_paged", "llm.spec_ngram_propose", "llm.spec_draft_steps"} <= names
    assert {
        "llm.disagg_extract_slots", "llm.disagg_extract_paged",
        "llm.disagg_scatter_slots", "llm.disagg_scatter_paged",
    } <= names
    assert {
        "llm.fused_step_int8", "llm.paged_fused_step_int8",
        "llm.spec_verify_int8", "llm.spec_verify_paged_int8",
        "llm.disagg_extract_slots_int8", "llm.disagg_extract_paged_int8",
        "llm.disagg_scatter_slots_int8", "llm.disagg_scatter_paged_int8",
    } <= names
    assert {
        "llm.fused_step_tp", "llm.fused_step_tp_int8c", "llm.paged_fused_step_tp",
        "llm.spec_verify_tp", "llm.spec_verify_paged_tp",
    } <= names
    assert {"llm.kvplane_wire_quantize", "llm.kvplane_wire_dequantize"} <= names
    assert {"llm.paged_attn_pallas", "llm.paged_attn_pallas_int8"} <= names
    # the tp entries declare their mesh axis, so JXC005 has teeth on them
    by_name = {e.name: e for e in entries}
    assert all(by_name[n].mesh_axes == ("tp",) for n in (
        "llm.fused_step_tp", "llm.fused_step_tp_int8c", "llm.paged_fused_step_tp",
        "llm.spec_verify_tp", "llm.spec_verify_paged_tp",
    ))


def test_cli_jax_flag_and_rt_wiring():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        # the AST pass over a small subtree (test_module_entrypoint_and_rt_wiring lints the whole one); --jax traces every entry whatever the path
        [sys.executable, "-m", "ray_tpu.scripts.cli", "lint", "ray_tpu/ops", "--root", ROOT, "--jax"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    m = re.search(r"jaxcheck traced (\d+) entry point", r.stderr)
    assert m and int(m.group(1)) >= 30, r.stderr


def test_cli_list_rules_includes_jax_catalog(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("TPL001", "TPL007", "CCR001", "CCR006", "JXC001", "JXC006"):
        assert rid in out
    assert "TPL004" not in out.replace("alias: TPL004", "")  # retired id only as alias


def test_lint_gate_script_noop_without_changes(tmp_path):
    # the CI gate must not die on a repo with no diff (e.g. a fresh clone)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "lint_gate.py"), "--base", "HEAD"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr


# ------------------------------------------------------------- json format
def test_cli_format_json_is_one_finding_per_line(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import ray_tpu\n\n"
        "async def h(ref):\n"
        "    return ray_tpu.get(ref)\n\n"
        "def drop(f):\n"
        "    f.remote()\n"
    )
    assert lint_main([str(bad), "--root", str(tmp_path), "--no-baseline", "--format=json"]) == 1
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert len(lines) == 2
    rules = set()
    for ln in lines:
        doc = json.loads(ln)  # every line parses on its own
        assert {"rule", "path", "line", "fingerprint", "message"} <= set(doc)
        assert doc["path"] == "bad.py" and len(doc["fingerprint"]) == 16
        rules.add(doc["rule"])
    assert rules == {"TPL001", "TPL002"}


def test_cli_format_json_reports_stale_entries(tmp_path, capsys):
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({
        "version": 1, "tool": "tpulint",
        "entries": {"feedfacefeedface": {
            "rule": "TPL006", "path": "ray_tpu/ops/layers.py",
            "context": "nope", "message": "never existed", "count": 1,
        }},
    }))
    assert lint_main([os.path.join(PKG, "ops"), "--root", ROOT,
                      "--baseline", str(stale), "--format=json"]) == 1
    docs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert any(d.get("stale") and d.get("fingerprint") == "feedfacefeedface" for d in docs)


# ------------------------------------------ baseline merge semantics (PR 2)
def _entries(path):
    return json.loads(path.read_text())["entries"]


def test_update_baseline_with_select_keeps_out_of_coverage_verbatim(tmp_path):
    """--update-baseline restricted by --select must keep every entry for
    deselected rules byte-for-byte, even in the same files. (Scoped to
    ray_tpu/core — where the checked-in baseline's entries live — to keep
    the tier-1 wall-clock down; coverage semantics are path-independent.)"""
    core = os.path.join(PKG, "core")
    out = tmp_path / "bl.json"
    assert lint_main([core, "--root", ROOT, "--baseline", str(out), "--update-baseline"]) == 0
    before = _entries(out)
    assert any(e["rule"] != "TPL001" for e in before.values()), "fixture needs non-TPL001 entries"
    # TPL001-only accept: every non-TPL001 entry is outside coverage
    assert lint_main([core, "--root", ROOT, "--baseline", str(out),
                      "--select", "TPL001", "--update-baseline"]) == 0
    after = _entries(out)
    assert {fp: e for fp, e in after.items() if e["rule"] != "TPL001"} == \
           {fp: e for fp, e in before.items() if e["rule"] != "TPL001"}
    # and the full run against the merged file is still clean
    assert lint_main([core, "--root", ROOT, "--baseline", str(out)]) == 0


def test_update_baseline_drops_stale_only_inside_coverage(tmp_path):
    """A stale entry is dropped by an update that COVERS it and kept
    verbatim (never resurrected, never duplicated) by one that doesn't."""
    core = os.path.join(PKG, "core")
    out = tmp_path / "bl.json"
    assert lint_main([core, "--root", ROOT, "--baseline", str(out), "--update-baseline"]) == 0
    doc = json.loads(out.read_text())
    ghost = {"rule": "TPL006", "path": "ray_tpu/core/node_agent.py",
             "context": "ghost", "message": "no longer reproduces", "count": 1}
    doc["entries"]["feedfacefeedface"] = ghost
    out.write_text(json.dumps(doc))
    # TPL001-only update: the TPL006 ghost is out of coverage -> kept verbatim
    assert lint_main([core, "--root", ROOT, "--baseline", str(out),
                      "--select", "TPL001", "--update-baseline"]) == 0
    assert _entries(out).get("feedfacefeedface") == ghost
    # TPL006-covering update over its tree: ghost is stale -> dropped
    assert lint_main([core, "--root", ROOT, "--baseline", str(out),
                      "--select", "TPL006", "--update-baseline"]) == 0
    assert "feedfacefeedface" not in _entries(out)
    # ...and a later out-of-coverage update must NOT resurrect it
    assert lint_main([core, "--root", ROOT, "--baseline", str(out),
                      "--select", "TPL001", "--update-baseline"]) == 0
    assert "feedfacefeedface" not in _entries(out)
    assert lint_main([core, "--root", ROOT, "--baseline", str(out)]) == 0


def test_lint_gate_tolerates_git_hook_args(tmp_path):
    # git invokes pre-push hooks as `hook <remote> <url>`; the documented
    # symlink install must not argparse-error on those positionals
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "lint_gate.py"),
         "--base", "HEAD", "origin", "ssh://example/repo.git"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_jax_only_select_skips_ast_but_validates_paths(tmp_path):
    # a jax-only --select must not die on "no rules match", and a typo'd
    # path is still a usage error even though the AST pass is skipped
    assert lint_main([str(tmp_path / "nope"), "--root", ROOT, "--jax", "--select", "JXC001"]) == 2


# ============================================================ fault gate
def test_err_self_check_clean_modulo_baseline(tree_findings):
    """The fault-discipline pass over ray_tpu/ itself: every swallowed
    exception / non-taxonomy raise / dropped cause chain / unbounded
    retry or transport wait is either fixed or a baseline entry with a
    hand-written why (the deliberate ones: the direct plane's best-effort
    probes, telemetry's never-load-bearing emits, the proxies'
    gone-client closes). Any NEW ERR finding fails tier-1."""
    from ray_tpu.lint.fault import fault_rule_ids

    findings = [f for f in tree_findings if f.rule in fault_rule_ids()]
    err_ids = fault_rule_ids() | {"TPL007"}
    entries = {fp: e for fp, e in bl.load(bl.default_baseline_path()).items()
               if e["rule"] in err_ids}
    d = bl.diff(findings, entries)
    assert d.new == [], (
        "NEW fault-discipline hazards in ray_tpu/ (fix, inline-disable "
        "with a rationale, or accept with --update-baseline + a why):\n"
        + "\n".join(f.render() for f in d.new)
    )
    assert d.stale == [], d.stale
    # the deliberate swallows stay TRACKED, not invisible
    assert d.suppressed >= 20


def test_err_baseline_entries_all_carry_written_whys():
    """Every accepted ERR entry must explain itself: a hand-written why
    that names the degradation path (not a placeholder) — the ledger is
    the documentation of every place the typed-error contract is waived."""
    from ray_tpu.lint.fault import fault_rule_ids

    err_ids = fault_rule_ids() | {"TPL007"}
    ents = [e for e in bl.load(bl.default_baseline_path()).values()
            if e["rule"] in err_ids]
    assert ents, "ERR catalog has no accepted entries? the self-app run found 20+"
    for e in ents:
        why = e.get("why") or ""
        assert why.startswith("deliberate:") and len(why) > 40, (
            f"ERR baseline entry without a real why: {e}"
        )


def test_cli_fault_flag_scopes_to_err_catalog(tmp_path, capsys):
    # --fault over the subtree that holds the baseline's entries runs clean against the committed baseline
    assert lint_main([CORE, "--root", ROOT, "--fault"]) == 0
    # and it implies the ERR selection: a TPL002 drop is NOT reported...
    bad = tmp_path / "bad.py"
    bad.write_text("def kick(actor):\n    actor.ping.remote()\n")
    assert lint_main([str(bad), "--root", str(tmp_path), "--no-baseline", "--fault"]) == 0
    # ...while an ERR001 conn swallow in the same run IS
    bad2 = tmp_path / "bad2.py"
    bad2.write_text(
        "def send(sock, data, actor):\n"
        "    actor.ping.remote()\n"
        "    try:\n"
        "        sock.sendall(data)\n"
        "    except ConnectionError:\n"
        "        pass\n"
    )
    assert lint_main([str(bad2), "--root", str(tmp_path), "--no-baseline",
                      "--fault", "--format=json"]) == 1
    docs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert {d["rule"] for d in docs} == {"ERR001"}


def test_cli_select_tpl007_alias_runs_err001(tmp_path, capsys):
    # pre-absorption --select specs keep working: TPL007 selects ERR001,
    # and the finding carries the CANONICAL id
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def send(sock, data):\n"
        "    try:\n"
        "        sock.sendall(data)\n"
        "    except ConnectionError:\n"
        "        pass\n"
    )
    assert lint_main([str(bad), "--root", str(tmp_path), "--no-baseline",
                      "--select", "TPL007", "--format=json"]) == 1
    docs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert docs and {d["rule"] for d in docs} == {"ERR001"}


def test_chaos_coverage_gate_catches_untested_fault_mode(tmp_path):
    """lint_gate's chaos-coverage check: a FAULT_MODES name that is not
    exercised in tests/test_llm_chaos.py (or an unregistered one) fails
    the gate — checked by probing the gate's checker directly."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import lint_gate
    finally:
        sys.path.pop(0)
    assert lint_gate.check_chaos_coverage() == []
