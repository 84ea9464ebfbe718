"""graftlint (tools/graftlint): fixture corpus per rule + the tier-1
self-clean gate.

Layout per rule: a known-bad fixture where the rule must fire (with the
expected count), a known-good twin where it must stay silent, plus the
shared suppression fixture. The self-clean gate — ``graftlint mxtpu/`` has
zero unsuppressed findings — is the test every future PR inherits: add a
trace-time lever without a policy_key entry, an unregistered jax.jit, or
an undocumented env var, and this file fails before a chip ever sees the
bug. No jax import needed: the analyzer is pure stdlib ast."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:  # pytest rootdir variants
    sys.path.insert(0, str(REPO))

from tools.graftlint import LintConfig, run  # noqa: E402
from tools.graftlint.rules import ALL_RULE_IDS  # noqa: E402

FIXTURES = REPO / "tests" / "fixtures" / "graftlint"


def fixture_config(**over):
    base = dict(
        root=FIXTURES,
        policy_key_module="registry_fixture.py",
        trace_scopes=("",),          # fixture tree: everything trace-time
        env_doc="env_doc_fixture.md",
        env_extra_roots=(),
        exclude=(),
        jit_allowlist={},
    )
    base.update(over)
    return LintConfig(**base)


def findings_of(path, rule, **over):
    res = run(fixture_config(**over), [path], [rule])
    return res


# ------------------------------------------------------- policy-key-coverage
def test_policy_key_bad_fires():
    res = findings_of("policy_key_bad.py", "policy-key-coverage")
    msgs = [f.message for f in res.findings]
    assert len(msgs) == 3, msgs
    assert any("MXTPU_BAZ" in m and "absent from" in m for m in msgs)
    assert any("MXTPU_BAR" in m and "'0'" in m and "'1'" in m for m in msgs)
    assert any("MXTPU_FOO" in m and "without a default" in m for m in msgs)


def test_policy_key_good_silent():
    res = findings_of("policy_key_good.py", "policy-key-coverage")
    assert res.findings == []


def test_policy_key_registry_module_not_blanket_exempt():
    # only the policy_key() FUNCTION is exempt; a stray trace-time read
    # elsewhere in the registry module itself must still fire
    res = findings_of("registry_fixture.py", "policy-key-coverage")
    msgs = [f.message for f in res.findings]
    assert len(msgs) == 1, msgs
    assert "MXTPU_STRAY" in msgs[0]
    assert not any("MXTPU_FOO" in m or "MXTPU_BAR" in m for m in msgs)


def test_policy_key_scope_gating():
    # outside the configured trace scopes, a missing lever is NOT flagged
    # (host-side trees may read MXTPU_* freely) but a default mismatch of
    # a key member still is
    res = findings_of("policy_key_bad.py", "policy-key-coverage",
                      trace_scopes=("some/other/tree",))
    msgs = [f.message for f in res.findings]
    assert not any("MXTPU_BAZ" in m for m in msgs)
    assert any("MXTPU_BAR" in m for m in msgs)


# ------------------------------------------- host-sync-in-traced-region
def test_host_sync_bad_fires():
    res = findings_of("host_sync_bad.py", "host-sync-in-traced-region")
    msgs = [f.message for f in res.findings]
    # pure: np.asarray + float + asnumpy + item; nested: asnumpy; bool
    assert len(msgs) == 6, msgs
    assert sum("asnumpy" in m for m in msgs) == 2
    assert any("np.asarray" in m for m in msgs)
    assert any("'float(...)'" in m for m in msgs)
    assert any("'.item()'" in m for m in msgs)
    assert any("'bool(...)'" in m for m in msgs)


def test_host_sync_good_silent():
    # shape arithmetic inside the jit and real syncs outside it are legal
    res = findings_of("host_sync_good.py", "host-sync-in-traced-region")
    assert res.findings == []


# ------------------------------------------------------------ use-after-donate
def test_donation_bad_fires():
    res = findings_of("donation_bad.py", "use-after-donate")
    msgs = [f.message for f in res.findings]
    assert len(msgs) == 4, msgs
    assert sum("'params'" in m for m in msgs) == 2  # incl. multi-line call
    assert any("'b'" in m for m in msgs)
    assert any("'state'" in m for m in msgs)  # via donate_argnames


def test_donation_good_silent():
    res = findings_of("donation_good.py", "use-after-donate")
    assert res.findings == []


# ------------------------------------------------ retrace-site-registration
def test_retrace_bad_fires():
    res = findings_of("retrace_bad.py", "retrace-site-registration")
    assert len(res.findings) == 2
    assert all("record_retrace" in f.message for f in res.findings)
    # the inventory names every site even when unregistered
    assert len(res.jit_inventory) == 2
    assert all(e["retrace_site"] is None for e in res.jit_inventory)


def test_retrace_good_silent_and_inventoried():
    res = findings_of("retrace_good.py", "retrace-site-registration")
    assert res.findings == []
    assert len(res.jit_inventory) == 1
    assert res.jit_inventory[0]["retrace_site"] == "fixture_site"


def test_service_seam_out_of_band_fires():
    """ISSUE 15: inside a service scope, a registered-but-private jit
    cache is a finding — it must resolve through compile_service."""
    res = findings_of("service_bad.py", "retrace-site-registration",
                      service_scopes=("",))
    assert len(res.findings) == 1
    assert "compile_service" in res.findings[0].message
    assert res.jit_inventory[0]["service"] is False
    # still registered: the watchdog sees it, only the service seam is
    # missing
    assert res.jit_inventory[0]["retrace_site"] == "fixture_site"


def test_service_seam_good_silent():
    res = findings_of("service_good.py", "retrace-site-registration",
                      service_scopes=("",))
    assert res.findings == []
    entry = res.jit_inventory[0]
    assert entry["service"] is True
    assert entry["retrace_site"] == "fixture_site"
    # the canonical_key call IS the declared cache-key expression
    assert "canonical_key" in entry["cache_key"]
    assert "policy" in entry["cache_key"]


def test_service_scope_gates_the_finding():
    """Outside the declared service scopes (default: mxtpu/) the plain
    record_retrace discipline stays sufficient — fixture trees and
    user code keep linting as before."""
    res = findings_of("service_bad.py", "retrace-site-registration")
    assert res.findings == []
    assert res.jit_inventory[0]["service"] is False


def test_retrace_allowlist():
    allow = {("retrace_bad.py", "compile_it"):
             {"site": "elsewhere", "reason": "counted by a caller",
              "cache_key": "declared-in-allowlist"}}
    res = findings_of("retrace_bad.py", "retrace-site-registration",
                      jit_allowlist=allow)
    # compile_it is allowlisted, one_off still fires
    assert len(res.findings) == 1
    assert "one_off" in res.findings[0].message
    entry = [e for e in res.jit_inventory if e["function"] == "compile_it"][0]
    assert entry["allowlisted"] and entry["retrace_site"] == "elsewhere"
    assert entry["cache_key"] == "declared-in-allowlist"


# ------------------------------------------------------------ env-var-catalog
def test_env_catalog_bad_fires():
    res = findings_of("env_catalog_bad.py", "env-var-catalog")
    by_path = {(f.path, f.message.split()[0]) for f in res.findings}
    assert ("env_catalog_bad.py", "MXTPU_UNDOCUMENTED") in by_path
    assert ("env_doc_fixture.md", "MXTPU_STALE") in by_path
    assert len(res.findings) == 2


def test_env_catalog_good_silent():
    res = findings_of("env_catalog_good.py", "env-var-catalog")
    assert res.findings == []


# ------------------------------------------------------- metric-name-catalog
def _metric_findings(path):
    return findings_of(path, "metric-name-catalog",
                       metric_doc="metric_doc_fixture.md",
                       metric_scopes=("",))


def test_metric_catalog_bad_fires():
    res = _metric_findings("metric_catalog_bad.py")
    msgs = [(f.path, f.message) for f in res.findings]
    assert len(msgs) == 3, msgs
    assert any(p == "metric_catalog_bad.py" and
               "'metric.undocumented'" in m for p, m in msgs)
    assert any(p == "metric_catalog_bad.py" and
               "'span.undocumented'" in m for p, m in msgs)
    assert any(p == "metric_doc_fixture.md" and "'metric.stale'" in m
               for p, m in msgs)


def test_metric_catalog_good_silent():
    # brace expansion, <i> placeholder vs %d pattern, tag annotation
    # stripping, the span d2h twin, and the retrace.<site> prefix all
    # reconcile — zero findings either direction
    res = _metric_findings("metric_catalog_good.py")
    assert res.findings == []


def test_metric_catalog_out_of_scope_collects_nothing():
    # with the default mxtpu/ scope the fixture file contributes no
    # names — and crucially the rule then issues NO stale-row verdicts
    # (a scoped-out run must not condemn the whole catalog)
    res = findings_of("metric_catalog_bad.py", "metric-name-catalog",
                      metric_doc="metric_doc_fixture.md")
    assert res.findings == []


# ---------------------------------------------------------------- suppressions
@pytest.mark.parametrize("rule,expected_suppressed", [
    ("policy-key-coverage", 1),
    ("host-sync-in-traced-region", 1),
    ("use-after-donate", 1),
    ("retrace-site-registration", 3),  # two inline + one disable=all
])
def test_inline_suppressions(rule, expected_suppressed):
    res = findings_of("suppressed.py", rule)
    assert res.findings == [], [f.format() for f in res.findings]
    assert len(res.suppressed) == expected_suppressed


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="unknown rule"):
        run(fixture_config(), ["policy_key_good.py"], ["no-such-rule"])


# ------------------------------------------------------------ the tier-1 gate
def _repo_result():
    return run(LintConfig(root=REPO), ["mxtpu"])


def test_self_clean_gate():
    """`python -m tools.graftlint mxtpu/` has ZERO unsuppressed findings.

    If this fails, fix the violation (or, for a genuinely host-side read /
    externally-counted jit site, add the inline suppression or allowlist
    entry WITH a reason) — do not baseline it."""
    res = _repo_result()
    assert res.findings == [], \
        "graftlint found violations:\n" + \
        "\n".join(f.format() for f in res.findings)


def test_all_rules_ran_over_repo():
    # the gate is only meaningful if every rule is registered and loaded
    assert set(ALL_RULE_IDS) == {
        "policy-key-coverage", "host-sync-in-traced-region",
        "use-after-donate", "retrace-site-registration",
        "env-var-catalog", "metric-name-catalog"}


def test_jit_surface_inventory_lists_all_six_caches():
    """The inventory is ROADMAP item 5's scouting report: all six jit
    caches (FusedUpdater, CachedOp, symbol executor, serving Predictor,
    serving DecodeEngine target family, serving DecodeEngine draft
    family) must appear with their retrace sites, and no
    site may be anonymous. Since ISSUE 7 the fused_optimizer cache is
    ALSO the mesh-native Trainer's cache — its declared key must carry
    the sharding component (MeshPlan fingerprint + per-buffer sharding
    tokens), the down payment on the unified compile-cache engine's key
    = fn + shapes + policy_key + sharding. Since ISSUE 8 the serving
    Predictor's site is per-INSTANCE (ReplicaSet members report at
    serving.predict.r<i>), so its inventory entry resolves through the
    JIT_ALLOWLIST declaration — which must name the per-replica caches
    to keep this report honest. Since ISSUE 11 the decode cache
    (serving.decode — step executables per cohort-capacity bucket +
    insert executables per prefill seq bucket) joins the same way: its
    declaration must spell out the AOT discipline (post-warmup compiles
    zero, donated carry). Since ISSUE 16 the speculative-decoding DRAFT
    cache (serving.draft — k-token proposal executables per cohort
    bucket) is the sixth entry: an out-of-band draft jit fails CI."""
    inv = _repo_result().jit_inventory
    sites = {e["retrace_site"] for e in inv}
    assert {"fused_optimizer", "cached_op", "executor",
            "executor.backward", "subgraph_exec", "parallel.train_step",
            "rtc", "serving.predict", "serving.decode",
            "serving.draft"} <= sites, sites
    assert None not in sites and "<dynamic>" not in sites
    # ISSUE 15: the unified compile service is under EVERY jit surface —
    # an inventory entry without the service seam is an out-of-band
    # cache (no LRU bound, no persistent executable cache, no AOT
    # warmup) and the rule fails CI on it inside mxtpu/
    assert all(e["service"] for e in inv), \
        [e for e in inv if not e["service"]]
    fused = [e for e in inv if e["retrace_site"] == "fused_optimizer"]
    # since ISSUE 18 the donation is a policy FUNCTION, not a literal:
    # (0, 2) everywhere except the XLA:CPU portable single-device class,
    # where serialized executables with input-output aliasing silently
    # corrupt when loaded in a fresh process (measured, jaxlib 0.4.37) —
    # the fleet's warm-rejoin disk cache depends on dropping donation
    # there. The inventory must still show ONE declared discipline.
    assert fused and all(e["donation"] == "donate_argnums=_donation()"
                         for e in fused)
    for e in fused:   # the merged mesh-trainer cache: sharding in the key
        assert "MeshPlan" in e["cache_key"], e["cache_key"]
        assert "sharding" in e["cache_key"], e["cache_key"]
    by_site = {e["retrace_site"]: e for e in inv}
    assert by_site["cached_op"]["file"] == "mxtpu/gluon/block.py"
    assert by_site["serving.predict"]["file"] == "mxtpu/serving/engine.py"
    assert "policy_key" in (by_site["cached_op"]["cache_key"] or "")
    serving = by_site["serving.predict"]
    assert serving["allowlisted"] is True
    # the per-replica jit caches are declared, not anonymous: the entry
    # names the serving.predict.r<i> site family and its bound
    assert "serving.predict.r" in serving["cache_key"], serving
    assert "policy_key" in serving["cache_key"], serving
    decode = by_site["serving.decode"]
    assert decode["file"] == "mxtpu/serving/decode.py", decode
    assert decode["allowlisted"] is True
    # the decode cache's contract rides the declaration: bucketed AOT
    # replay (zero post-warmup compiles) over donated carry state
    assert "policy_key" in decode["cache_key"], decode
    assert "bucket" in decode["cache_key"], decode
    assert "donated" in decode["cache_key"], decode
    # ISSUE 16: the paged step family rides the same front door (page
    # table as a traced argument, never a new executable) and the draft
    # cache is declared at its own site with the same AOT discipline
    assert "page_tokens" in decode["cache_key"], decode
    draft = by_site["serving.draft"]
    assert draft["file"] == "mxtpu/serving/decode.py", draft
    assert draft["allowlisted"] is True
    assert "policy_key" in draft["cache_key"], draft
    assert "spec_k" in draft["cache_key"], draft


# ------------------------------------------------------------------------ CLI
def _run_cli(args, cwd):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint"] + args,
        cwd=str(cwd), capture_output=True, text=True, timeout=120)


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "def f(fn):\n"
                   "    return jax.jit(fn)\n")
    good = tmp_path / "good.py"
    good.write_text("import jax\n"
                    "def f(fn):\n"
                    "    telemetry.record_retrace('s', {})\n"
                    "    return jax.jit(fn)\n")
    out = tmp_path / "report.json"
    proc = _run_cli(["bad.py", "--root", str(tmp_path),
                     "--rules", "retrace-site-registration",
                     "--json", str(out)], cwd=REPO)
    assert proc.returncode == 1, proc.stderr
    assert "retrace-site-registration" in proc.stdout
    payload = json.loads(out.read_text())
    assert len(payload["findings"]) == 1
    assert len(payload["jit_inventory"]) == 1

    proc = _run_cli(["good.py", "--root", str(tmp_path),
                     "--rules", "retrace-site-registration"], cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_self_clean_and_inventory(tmp_path):
    """The command-line self-clean invocation exits 0, and
    --inventory lands the scouting-report JSON."""
    inv = tmp_path / "jit_surfaces.json"
    proc = _run_cli(["mxtpu/", "--inventory", str(inv)], cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    entries = json.loads(inv.read_text())
    assert {e["retrace_site"] for e in entries} >= {
        "fused_optimizer", "cached_op", "executor", "serving.predict"}
