"""Unified static-analysis subsystem (ray_tpu/analysis): tier-1 gate +
engine/pass/baseline units.

This module replaces the five separate test_*_check.py entry points as
THE static-analysis gate (the old modules remain as thin aliases into
here so nothing silently drops):

  * live-tree gate — every registered pass runs clean under
    scripts/check_all.py (zero unbaselined findings, stale baseline
    entries fail);
  * verdict parity — each ported checker (RPC-IDEM, TRACE-PROP,
    SERVE-WAL, DAG-TEARDOWN, METRICS-CAT) reports IDENTICAL findings
    through the engine as through its historical script entry point;
  * per-pass fixtures — every new concurrency pass has true-positive
    and negative cases, planted under tmp_path (never the package dir —
    the PR 12 leaked-fixture lesson);
  * suppression/baseline units — inline noqa (with reasons), baseline
    matching, stale-entry failure, malformed-entry failure.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import check_all  # noqa: E402

_A = check_all.load_analysis()


def _pass_mod(name):
    return importlib.import_module(f"_rt_analysis.passes.{name}")


def _shim(name):
    return importlib.import_module(name)


_CACHED_REPORT = []
_CACHE = _A.ModuleCache()  # parsed modules shared by every run below


def _report():
    """One full-tree run shared by every live-tree assertion in this
    module AND the thin-alias modules (the tree doesn't change under a
    test session; re-walking ~200 files per aliased test was pure
    in-suite budget burn)."""
    if not _CACHED_REPORT:
        _CACHED_REPORT.append(_A.run(cache=_CACHE))
    return _CACHED_REPORT[0]


def rule_clean(rule):
    """Live-tree verdict for one rule, from the shared report."""
    return [f.render() for f in _report().active if f.rule == rule]


# ---------------------------------------------------------------------------
# Live-tree gate (the tier-1 wiring for ALL passes)
# ---------------------------------------------------------------------------

def test_live_tree_clean_under_check_all():
    """Zero unbaselined findings across every registered pass — the
    acceptance bar: the analysis subsystem gates tier-1 through this
    one test."""
    report = _report()
    assert report.errors == [], report.errors
    assert report.stale_baseline == [], report.stale_baseline
    assert [f.render() for f in report.active] == []


def test_check_all_script_json_contract():
    """The CLI entry point future CI consumes: exit 0 on a clean tree,
    machine-readable report with the stable key set. Scoped to two
    cheap rules — the all-pass clean gate runs in-process above; this
    test pins the subprocess/JSON contract without re-walking the tree
    in a cold process."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_all.py"),
         "--json", "--rule", "DAG-TEARDOWN", "--rule", "SERVE-WAL"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    for key in ("ok", "exit_code", "findings", "suppressed",
                "stale_baseline", "errors", "pass_counts"):
        assert key in report
    assert report["ok"] is True
    assert report["findings"] == []
    assert set(report["pass_counts"]) == {"DAG-TEARDOWN", "SERVE-WAL"}


def test_all_passes_registered():
    importlib.import_module(f"{check_all._PKG_NAME}.passes")
    passes = _A.all_passes()
    for rule in ("RPC-IDEM", "TRACE-PROP", "SERVE-WAL", "DAG-TEARDOWN",
                 "METRICS-CAT", "ASYNC-BLOCK", "AWAIT-LOCK",
                 "CANCEL-SAFE", "SEQLOCK-DISCIPLINE", "PUBSUB-ORDER"):
        assert rule in passes, f"pass {rule} not registered"


def test_unknown_rule_is_an_error():
    report = _A.run(rules=["NO-SUCH-RULE"])
    assert report.exit_code == 2
    assert any("NO-SUCH-RULE" in e for e in report.errors)


# ---------------------------------------------------------------------------
# Verdict parity: ported checkers == historical script entry points
# ---------------------------------------------------------------------------

_PORTED = [
    ("RPC-IDEM", "rpc_idempotency", "check_rpc_idempotency"),
    ("TRACE-PROP", "trace_propagation", "check_trace_propagation"),
    ("SERVE-WAL", "serve_persistence", "check_serve_persistence"),
    ("DAG-TEARDOWN", "dag_teardown", "check_dag_teardown"),
    ("METRICS-CAT", "metrics_catalog", "check_metrics_catalog"),
]


@pytest.mark.parametrize("rule,pass_name,script_name", _PORTED)
def test_ported_checker_parity(rule, pass_name, script_name):
    """The registered pass, the pass module's check(), and the
    historical script shim all report the same verdict on the live
    tree (clean — the pre-port checkers were green at HEAD), and the
    pass's findings are the check() strings verbatim."""
    shim_problems = _shim(script_name).check(cache=_CACHE)
    pass_problems = _pass_mod(pass_name).check(cache=_CACHE)
    assert shim_problems == pass_problems == []
    assert [f.message for f in _report().findings
            if f.rule == rule and not f.suppressed] == pass_problems


def test_rpc_checker_detects_unannotated_handler(tmp_path):
    checker = _shim("check_rpc_idempotency")
    p = tmp_path / "fake_daemon.py"
    p.write_text(
        "class S:\n"
        "    @rpc.idempotent\n"
        "    async def rpc_ok(self, conn, payload):\n"
        "        pass\n"
        "    async def rpc_gap(self, conn, payload):\n"
        "        pass\n")
    gaps = checker.handler_gaps(str(p))
    assert [g[0] for g in gaps] == ["rpc_gap"]


def test_trace_checker_detects_missing_forwarding(monkeypatch):
    mod = _pass_mod("trace_propagation")
    monkeypatch.setattr(mod, "RULES", mod.RULES + [
        ("ray_tpu/serve/proxy.py", "ProxyActor", "_handle_conn",
         [r"THIS_TOKEN_DOES_NOT_EXIST"], "synthetic gap")])
    problems = mod.check(cache=_CACHE)
    assert any("THIS_TOKEN_DOES_NOT_EXIST" in p for p in problems)


def test_trace_checker_detects_renamed_entry_point(monkeypatch):
    mod = _pass_mod("trace_propagation")
    monkeypatch.setattr(mod, "RULES", mod.RULES + [
        ("ray_tpu/serve/proxy.py", "ProxyActor", "_handle_conn_v2",
         [r"request_trace\.mint\("], "synthetic rename")])
    problems = mod.check(cache=_CACHE)
    assert any("_handle_conn_v2 not found" in p for p in problems)


def test_trace_checker_flags_raw_replica_dispatch(tmp_path):
    """The rogue fixture is planted in tmp_path — never the real
    package dir, where an interrupted run would leak it into the
    checkout (the PR 12 lesson)."""
    mod = _pass_mod("trace_propagation")
    rogue = tmp_path / "_rogue_dispatch_test.py"
    rogue.write_text("class Rogue:\n"
                     "    def go(self, replica):\n"
                     "        return replica.handle_request.remote('m')\n",
                     encoding="utf-8")
    problems = mod.check(cache=_CACHE,
                         extra_dispatch_dirs=[str(tmp_path)])
    assert any("_rogue_dispatch_test.py" in p for p in problems)
    # The shim forwards the kwarg too.
    problems = _shim("check_trace_propagation").check(
        extra_dispatch_dirs=[str(tmp_path)], cache=_CACHE)
    assert any("_rogue_dispatch_test.py" in p for p in problems)


def test_persistence_checker_detects_missing_persist(monkeypatch):
    mod = _pass_mod("serve_persistence")
    monkeypatch.setattr(mod, "ORDERED_RULES", mod.ORDERED_RULES + [
        ("ServeController", "deploy_app",
         r"THIS_PERSIST_CALL_DOES_NOT_EXIST", r"self\._deployments\[",
         "synthetic gap")])
    problems = mod.check(cache=_CACHE)
    assert any("THIS_PERSIST_CALL_DOES_NOT_EXIST" in p for p in problems)


def test_persistence_checker_detects_effect_before_persist(monkeypatch):
    mod = _pass_mod("serve_persistence")
    monkeypatch.setattr(mod, "ORDERED_RULES", [
        ("ServeController", "_deploy_app_locked",
         r"self\._persist\.put\(", r"incoming: Dict",
         "synthetic ordering violation")])
    problems = mod.check(cache=_CACHE)
    assert any("BEFORE persisting" in p for p in problems)


def test_teardown_checker_detects_missing_release(monkeypatch):
    mod = _pass_mod("dag_teardown")
    monkeypatch.setattr(mod, "ACQUIRE_RELEASE", mod.ACQUIRE_RELEASE + [
        (r"RingChannel\(", r"THIS_RELEASE_DOES_NOT_EXIST",
         "synthetic gap")])
    problems = mod.check(cache=_CACHE)
    assert any("THIS_RELEASE_DOES_NOT_EXIST" in p for p in problems)


def test_teardown_checker_detects_bad_order(monkeypatch):
    mod = _pass_mod("dag_teardown")
    monkeypatch.setattr(mod, "TEARDOWN_ORDER", [
        (r"\.destroy\(\)", r"\.close\(\)", "synthetic inversion")])
    problems = mod.check(cache=_CACHE)
    assert any("synthetic inversion" in p for p in problems)


def test_metrics_parser_sees_known_metrics():
    mod = _pass_mod("metrics_catalog")
    code = mod.code_metric_names(_CACHE)
    catalog = mod.catalog_metric_names(cache=_CACHE)
    assert "ray_tpu_task_phase_seconds" in code
    assert "ray_tpu_pubsub_dropped_total" in code
    assert len(catalog) >= 20


# ---------------------------------------------------------------------------
# ASYNC-BLOCK fixtures
# ---------------------------------------------------------------------------

def _scan(pass_name, tmp_path, source):
    mod = _pass_mod(pass_name)
    p = tmp_path / "fixture_mod.py"
    p.write_text(source, encoding="utf-8")
    cache = _A.ModuleCache(str(tmp_path))
    return mod.scan_paths([str(p)], cache), cache


ASYNC_BLOCK_FIXTURE = """\
import asyncio
import time


def helper():
    time.sleep(1)


def indirect():
    helper()


async def bad_direct():
    time.sleep(0.1)


async def bad_result(fut):
    return fut.result()


async def bad_transitive():
    indirect()


async def bad_noqa():
    time.sleep(0.1)  # ray-tpu: noqa(ASYNC-BLOCK): fixture reason text


async def ok_async_sleep():
    await asyncio.sleep(0.1)


async def ok_executor(loop):
    await loop.run_in_executor(None, helper)


async def ok_nested_def():
    def inner():
        time.sleep(1)
    return inner
"""


def test_async_block_positives_and_negatives(tmp_path):
    findings, _cache = _scan("blocking_async", tmp_path,
                             ASYNC_BLOCK_FIXTURE)
    by_fn = {}
    for f in findings:
        fn = f.key.split("::")[0]
        by_fn.setdefault(fn, []).append(f)
    assert "bad_direct" in by_fn            # direct time.sleep
    assert "bad_result" in by_fn            # .result() wait
    assert "bad_transitive" in by_fn        # helper chain
    assert "bad_noqa" in by_fn              # scan sees it; noqa below
    for ok in ("ok_async_sleep", "ok_executor", "ok_nested_def"):
        assert ok not in by_fn, by_fn[ok]
    # The transitive finding names the chain.
    assert "time.sleep" in by_fn["bad_transitive"][0].message


def test_async_block_noqa_suppresses_with_reason(tmp_path):
    findings, cache = _scan("blocking_async", tmp_path,
                            ASYNC_BLOCK_FIXTURE)
    _A.apply_noqa(findings, cache)
    noqa = [f for f in findings if f.key.startswith("bad_noqa")]
    assert noqa and all(f.suppressed for f in noqa)
    assert noqa[0].reason == "fixture reason text"
    others = [f for f in findings if not f.key.startswith("bad_noqa")]
    assert others and not any(f.suppressed for f in others)


def test_async_block_helper_noqa_cuts_the_chain(tmp_path):
    src = ASYNC_BLOCK_FIXTURE.replace(
        "def helper():\n    time.sleep(1)",
        "def helper():\n    # ray-tpu: noqa(ASYNC-BLOCK): bounded\n"
        "    time.sleep(1)")
    findings, _cache = _scan("blocking_async", tmp_path, src)
    fns = {f.key.split("::")[0] for f in findings}
    # One justification at the helper's blocking line clears every
    # async caller of the chain; direct calls still flag.
    assert "bad_transitive" not in fns
    assert "bad_direct" in fns


# ---------------------------------------------------------------------------
# AWAIT-LOCK fixtures
# ---------------------------------------------------------------------------

AWAIT_LOCK_FIXTURE = """\
import asyncio
import threading


class C:
    def __init__(self):
        self._tlock = threading.Lock()
        self._alock = asyncio.Lock()
        self._items = {}

    async def bad_thread_hold(self):
        with self._tlock:
            await asyncio.sleep(0.1)

    async def bad_local_thread_hold(self):
        lock = threading.Lock()
        with lock:
            await asyncio.sleep(0.1)

    async def bad_straddle(self):
        async with self._alock:
            self._items["a"] = 1
            await asyncio.sleep(0.1)
            self._items["b"] = 2

    async def ok_async_hold(self):
        async with self._alock:
            await asyncio.sleep(0.1)

    async def ok_thread_no_await(self):
        with self._tlock:
            self._items.clear()

    async def ok_straddle_distinct_attrs(self):
        async with self._alock:
            self._before = 1
            await asyncio.sleep(0)
            self._after = 2

    async def ok_unresolvable_ctx(self, mystery):
        with mystery:
            await asyncio.sleep(0)

    async def ok_nested_closure_under_lock(self):
        with self._tlock:
            async def cb():
                await asyncio.sleep(0)
            self._cb = cb

    async def ok_nested_closure_straddle(self):
        async with self._alock:
            self._items["a"] = 1
            async def cb():
                await asyncio.sleep(0)
            self._items["b"] = 2
            self._cb2 = cb
"""


def test_await_lock_positives_and_negatives(tmp_path):
    findings, _cache = _scan("await_under_lock", tmp_path,
                             AWAIT_LOCK_FIXTURE)
    fns = {f.key.split("::")[0].split(".")[-1] for f in findings}
    assert "bad_thread_hold" in fns
    assert "bad_local_thread_hold" in fns
    assert "bad_straddle" in fns
    for ok in ("ok_async_hold", "ok_thread_no_await",
               "ok_straddle_distinct_attrs", "ok_unresolvable_ctx",
               "ok_nested_closure_under_lock",
               "ok_nested_closure_straddle"):
        assert ok not in fns
    straddle = [f for f in findings if "bad_straddle" in f.key][0]
    assert "_items" in straddle.message


# ---------------------------------------------------------------------------
# CANCEL-SAFE fixtures
# ---------------------------------------------------------------------------

CANCEL_SAFE_FIXTURE = """\
import asyncio


class R:
    async def bad_plain(self, pool):
        pool.acquire()
        await asyncio.sleep(0.1)
        pool.release()

    async def bad_except_exception(self, pool):
        pool.acquire()
        try:
            await asyncio.sleep(0.1)
        except Exception:
            pool.release()
            raise

    async def ok_finally(self, pool):
        pool.acquire()
        try:
            await asyncio.sleep(0.1)
        finally:
            pool.release()

    async def ok_base_exception(self, pool):
        pool.acquire()
        try:
            await asyncio.sleep(0.1)
        except BaseException:
            pool.release()
            raise

    async def ok_no_release(self, pool):
        pool.acquire()
        await asyncio.sleep(0.1)

    async def _shielded_section(self, pool):
        pool.acquire()
        await asyncio.sleep(0.1)
        pool.release()

    async def caller(self, pool):
        await asyncio.shield(self._shielded_section(pool))

    async def ok_release_before_await(self, pool):
        pool.acquire()
        pool.release()
        await asyncio.sleep(0.1)
"""


def test_cancel_safe_positives_and_negatives(tmp_path):
    findings, _cache = _scan("cancellation_safety", tmp_path,
                             CANCEL_SAFE_FIXTURE)
    fns = {f.key.split("::")[0].split(".")[-1] for f in findings}
    assert "bad_plain" in fns
    assert "bad_except_exception" in fns   # Exception misses Cancelled
    for ok in ("ok_finally", "ok_base_exception", "ok_no_release",
               "_shielded_section", "ok_release_before_await"):
        assert ok not in fns, sorted(fns)


def test_cancel_safe_release_via_helper_counts(tmp_path):
    src = """\
import asyncio


class R:
    def _cleanup_release(self, pool):
        pool.release()

    async def ok_helper_finally(self, pool):
        pool.acquire()
        try:
            await asyncio.sleep(0.1)
        finally:
            self._cleanup_release(pool)
"""
    findings, _cache = _scan("cancellation_safety", tmp_path, src)
    assert findings == []


# ---------------------------------------------------------------------------
# Engine units
# ---------------------------------------------------------------------------

def test_engine_import_alias_resolution(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("import time as t\n"
                 "from threading import Lock as L\n"
                 "import asyncio\n"
                 "def f():\n"
                 "    t.sleep(1)\n"
                 "    x = L()\n")
    cache = _A.ModuleCache(str(tmp_path))
    mod = cache.get(str(p))
    assert mod.imports()["t"] == "time"
    assert mod.imports()["L"] == "threading.Lock"
    import ast as _ast
    calls = [n for n in _ast.walk(mod.tree) if isinstance(n, _ast.Call)]
    names = {mod.call_name(c) for c in calls}
    assert "time.sleep" in names
    assert "threading.Lock" in names


def test_engine_same_file_base_class_resolution(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("class Base:\n"
                 "    def close(self):\n"
                 "        pass\n"
                 "class Child(Base):\n"
                 "    def destroy(self):\n"
                 "        self.close()\n")
    cache = _A.ModuleCache(str(tmp_path))
    mod = cache.get(str(p))
    methods = mod.class_methods("Child")
    assert set(methods) == {"close", "destroy"}
    # Transitive source follows self-calls into the inherited method.
    src = mod.transitive_source(methods, "destroy")
    assert "def close" in src


def test_engine_finding_key_is_line_stable():
    f1 = _A.Finding("R", "a.py", 10, "x.py:10: thing broke")
    f2 = _A.Finding("R", "a.py", 99, "x.py:99: thing broke")
    assert f1.key == f2.key
    assert f1.ident == f2.ident


def test_engine_noqa_parsing(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("x = 1  # ray-tpu: noqa(MY-RULE): because reasons\n"
                 "# ray-tpu: noqa(OTHER)\n"
                 "y = 2\n"
                 "z = 3\n")
    cache = _A.ModuleCache(str(tmp_path))
    mod = cache.get(str(p))
    assert mod.noqa_at(1, "MY-RULE") == "because reasons"
    assert mod.noqa_at(1, "OTHER") is None       # rule must match
    assert mod.noqa_at(3, "OTHER") == ""         # line above, no reason
    assert mod.noqa_at(4, "OTHER") is None


# ---------------------------------------------------------------------------
# Baseline units
# ---------------------------------------------------------------------------

def test_baseline_match_suppresses_and_carries_why():
    f = _A.Finding("RULE-X", "pkg/m.py", 5, "m broke", key="k1")
    stale = _A.apply_baseline(
        [f], [{"rule": "RULE-X", "file": "pkg/m.py", "key": "k1",
               "why": "accepted debt"}])
    assert stale == []
    assert f.suppressed and f.reason == "baseline: accepted debt"


def test_baseline_entry_suppresses_exactly_one_finding():
    """Keys are line-independent, so a second violation with the same
    key (another blocking call added to an already-waived function)
    must still fail the run instead of riding the old waiver."""
    f1 = _A.Finding("RULE-X", "pkg/m.py", 5, "m broke at 5", key="k1")
    f2 = _A.Finding("RULE-X", "pkg/m.py", 9, "m broke at 9", key="k1")
    stale = _A.apply_baseline(
        [f1, f2], [{"rule": "RULE-X", "file": "pkg/m.py", "key": "k1",
                    "why": "accepted debt"}])
    assert stale == []
    assert [f.suppressed for f in (f1, f2)] == [True, False]


def test_baseline_stale_entry_fails():
    f = _A.Finding("RULE-X", "pkg/m.py", 5, "m broke", key="k1")
    stale = _A.apply_baseline(
        [f], [{"rule": "RULE-X", "file": "pkg/m.py", "key": "GONE",
               "why": "fixed long ago"}])
    assert len(stale) == 1 and "stale baseline" in stale[0]


def test_baseline_stale_entry_fails_the_full_run(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"entries": [
        {"rule": "ASYNC-BLOCK", "file": "ray_tpu/_private/gcs.py",
         "key": "NoSuch.fn::nothing", "why": "stale on purpose"}]}))
    report = _A.run(rules=["ASYNC-BLOCK"], baseline_path=str(bl),
                    cache=_CACHE)
    assert report.exit_code == 1
    assert any("stale" in s for s in report.stale_baseline)


def test_baseline_malformed_entry_is_an_error(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"entries": [
        {"rule": "ASYNC-BLOCK", "file": "x.py", "key": "k"}]}))  # no why
    report = _A.run(rules=["ASYNC-BLOCK"], baseline_path=str(bl),
                    cache=_CACHE)
    assert report.exit_code == 2
    assert any("why" in e for e in report.errors)


def test_live_baseline_entries_all_match():
    """Every entry in the committed baseline matches a live finding —
    asserted by the clean-tree test too, but this one names the file so
    a stale entry fails with a pointed message."""
    entries = _A.load_baseline()
    report = _report()
    assert report.stale_baseline == [], (
        "scripts/analysis_baseline.json has stale entries: "
        f"{report.stale_baseline}")
    baselined = [f for f in report.suppressed
                 if f.reason.startswith("baseline: ")]
    assert len(baselined) == len(entries)


# ---------------------------------------------------------------------------
# SEQLOCK-DISCIPLINE (shm channel readers vs torn reads)
# ---------------------------------------------------------------------------

SEQLOCK_FIXTURE = """\
import struct

H = struct.Struct("<QQ")


class NoRecheck:
    def read(self):
        version, length = H.unpack_from(self._buf, 0)
        payload = bytes(self._buf[16:16 + length])
        self._local_cursor = version
        return payload


class PartialRecheck:
    def read(self):
        version, length = H.unpack_from(self._buf, 0)
        payload = bytes(self._buf[16:16 + length])
        v2, l2 = H.unpack_from(self._buf, 0)
        if v2 == version:
            self._local_cursor = version
        return payload


class UnguardedAdvance:
    def read(self):
        version, length = H.unpack_from(self._buf, 0)
        payload = bytes(self._buf[16:16 + length])
        v2, l2 = H.unpack_from(self._buf, 0)
        if v2 == version and l2 == length:
            ok = payload
        # ray-tpu: noqa(SEQLOCK-DISCIPLINE): fixture reason text
        self._set_cursor(0, version)
        return payload


class CleanReader:
    def read(self):
        version, length = H.unpack_from(self._buf, 0)
        payload = bytes(self._buf[16:16 + length])
        v2, l2 = H.unpack_from(self._buf, 0)
        if v2 == version and l2 == length:
            self._set_cursor(0, version)
            return payload


class WriterOnly:
    def write(self, data):
        version, _ = H.unpack_from(self._buf, 0)
        H.pack_into(self._buf, 0, version + 1, len(data))
"""


def test_seqlock_positives_and_negatives(tmp_path):
    findings, _cache = _scan("seqlock_discipline", tmp_path,
                             SEQLOCK_FIXTURE)
    by_key = {f.key: f for f in findings}
    assert "NoRecheck.read::no-recheck" in by_key
    assert "PartialRecheck.read::partial-recheck" in by_key
    assert any(k.startswith("UnguardedAdvance.read::unguarded-advance")
               for k in by_key)
    # Clean reader and the cursor-less writer never flag.
    assert not any(k.startswith(("CleanReader", "WriterOnly"))
                   for k in by_key), by_key


def test_seqlock_noqa_suppresses_with_reason(tmp_path):
    findings, cache = _scan("seqlock_discipline", tmp_path,
                            SEQLOCK_FIXTURE)
    _A.apply_noqa(findings, cache)
    unguarded = [f for f in findings
                 if f.key.startswith("UnguardedAdvance")]
    assert unguarded and all(f.suppressed for f in unguarded)
    assert unguarded[0].reason == "fixture reason text"
    others = [f for f in findings if not f.key.startswith("Unguarded")]
    assert others and not any(f.suppressed for f in others)


def test_seqlock_recognizes_live_readers():
    """The pass must actually classify the shipping channel readers as
    seqlock readers (a predicate drift that skips them would make the
    live-tree gate vacuous) — and find them clean."""
    sq = _pass_mod("seqlock_discipline")
    readers = set()
    for rel in ("ray_tpu/experimental/channel.py",
                "ray_tpu/experimental/channels.py"):
        mod = _CACHE.get(rel)
        for (cls, fn), (node, _s, _l) in mod.functions().items():
            if sq._cursor_advances(node) and sq._tuple_unpacks(node):
                readers.add((cls, fn))
    assert ("Channel", "read") in readers
    assert ("RingReader", "read") in readers
    assert rule_clean("SEQLOCK-DISCIPLINE") == []


# ---------------------------------------------------------------------------
# PUBSUB-ORDER (publish-after-state-write discipline, gcs.py)
# ---------------------------------------------------------------------------

PUBSUB_FIXTURE = """\
class Gcs:
    def __init__(self):
        self.pubsub = Pubsub()

    async def ok_sync_run(self, payload):
        self.nodes[payload["id"]] = payload
        self.pubsub.publish("nodes", {"event": "alive"})
        await self.clients.request("x", "y", {})

    async def bad_write_await_publish(self, payload):
        self.nodes.pop(payload["id"], None)
        await self.clients.request("addr", "kill", {})
        self.pubsub.publish("nodes", {"event": "dead"})

    async def ok_early_exit_branch(self, payload):
        self.counters.pop("k", None)
        if payload.get("dead"):
            await self.rollback()
            return
        self.pubsub.publish("nodes", {"event": "alive"})

    async def bad_split_fanout(self, payload):
        self.pubsub.publish("nodes", {"event": "gang", "n": 2})
        await self.flush()
        self.pubsub.publish("nodes", {"event": "draining"})

    async def ok_mixed_channels(self, payload):
        self.pubsub.publish("nodes", {"event": "dead"})
        await self.flush()
        self.pubsub.publish("actors", {"event": "dead"})

    async def bad_suppressed(self, payload):
        self.jobs["j"] = payload
        await self.flush()
        # ray-tpu: noqa(PUBSUB-ORDER): fixture reason text
        self.pubsub.publish("jobs", {"event": "finished"})

    async def ok_write_is_await_result(self, payload):
        self.stats = await self.collect()
        self.pubsub.publish("nodes", {"event": "stats"})
"""


def test_pubsub_order_positives_and_negatives(tmp_path):
    findings, _cache = _scan("pubsub_ordering", tmp_path, PUBSUB_FIXTURE)
    keys = {f.key for f in findings}
    assert ("Gcs.bad_write_await_publish::write-await-publish::nodes"
            in keys), keys
    assert "Gcs.bad_split_fanout::publish-await-publish::nodes" in keys
    assert ("Gcs.bad_suppressed::write-await-publish::jobs" in keys)
    # Clean shapes: publish in the write's synchronous run, early-exit
    # rollback branches, different channels, write-from-await-result.
    assert not any(k.startswith("Gcs.ok_") for k in keys), keys


def test_pubsub_order_noqa_suppresses_with_reason(tmp_path):
    findings, cache = _scan("pubsub_ordering", tmp_path, PUBSUB_FIXTURE)
    _A.apply_noqa(findings, cache)
    supp = [f for f in findings if f.key.startswith("Gcs.bad_suppressed")]
    assert supp and all(f.suppressed for f in supp)
    assert supp[0].reason == "fixture reason text"
    others = [f for f in findings
              if not f.key.startswith("Gcs.bad_suppressed")]
    assert others and not any(f.suppressed for f in others)


def test_pubsub_order_live_tree_clean():
    """gcs.py's publish sites all ride the synchronous run of the state
    write they announce (the kill-actor and remove-pg publishes were
    hoisted above their slow RPC awaits when this pass landed)."""
    assert rule_clean("PUBSUB-ORDER") == []


# ---------------------------------------------------------------------------
# The families' tests: one body a check, one row a family
# ---------------------------------------------------------------------------

def test_every_family_has_its_row_and_no_test_body_is_copied():
    """tests/helpers/families.py holds the checks every family has, once.
    (a) Every module under benchmark/families/ that a configuration names
    (the cells' and the rehearsal's) has its row in FILES, and the file the
    row names defines FAMILY for that module. (b) No two files under tests/
    define a test of one name with one body: a new family imports the shared
    check and gives it data, it does not copy the last family's file."""
    import ast
    import collections
    import glob

    tests = os.path.join(REPO, "tests")
    sys.path.insert(0, tests)
    try:
        families = importlib.import_module("helpers.families")
        named = set()
        for path in glob.glob(os.path.join(REPO, "benchmark", "configs",
                                           "*.json")) + glob.glob(
                os.path.join(REPO, "benchmark", "rehearsal", "configs",
                             "*.json")):
            with open(path) as f:
                named.add(json.load(f).get("family", "gpt_dense"))
        assert named and named <= set(families.FILES), \
            named - set(families.FILES)
        for name in named:
            assert os.path.exists(os.path.join(
                REPO, "benchmark", "families", name + ".py")), name
            if families.FILES[name] is None:
                continue
            assert os.path.exists(os.path.join(tests, families.FILES[name]))
            row = importlib.import_module(families.FILES[name][:-3]).FAMILY
            assert isinstance(row, families.Family) and row.name == name
    finally:
        sys.path.remove(tests)

    defined = collections.defaultdict(list)
    for path in sorted(glob.glob(os.path.join(tests, "test_*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name.startswith("test_"):
                body = node.body
                if ast.get_docstring(node) is not None:
                    body = body[1:]
                defined[node.name, "".join(map(ast.dump, body))].append(
                    os.path.basename(path))
    copied = {name: files for (name, _body), files in defined.items()
              if len(files) > 1}
    assert not copied, copied


def test_the_heavy_files_are_placed_first_by_what_they_take():
    """tests/conftest.py's order of the files, a plain function of what the
    collector sees of each (here: read off the source, nothing collected or
    compiled): every family's file (helpers/families.py:FILES) and every
    file that takes `cell_step` stands before every file that is neither,
    a `cell_step` one and another in turn; the order is the same from any order
    of the input, which xdist's workers need; and the hook moves whole
    files and keeps a file's own order, and xdist's reordering of the files
    by their count of tests is switched off where its scheduler reads it."""
    import ast
    import glob
    import random
    import types

    tests = os.path.join(REPO, "tests")
    sys.path.insert(0, tests)
    try:
        conftest = importlib.import_module("conftest")
        families = importlib.import_module("helpers.families")
    finally:
        sys.path.remove(tests)
    files = {}
    for path in glob.glob(os.path.join(tests, "test_*.py")):
        with open(path) as f:
            tree = ast.parse(f.read())
        names = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                names.update(a.asname or a.name for a in node.names)
            elif isinstance(node, ast.Assign):
                names.update(t.id for t in node.targets
                             if isinstance(t, ast.Name))
            elif isinstance(node, ast.FunctionDef) \
                    and node.name.startswith("test_"):
                names.update(a.arg for a in node.args.args)
        files[os.path.basename(path)] = ("FAMILY" in names, names)
    order = conftest.heavy_first(files)
    assert sorted(order) == sorted(files)
    heavy = {f for f in files if "cell_step" in files[f][1]} | {
        f for f in families.FILES.values() if f}
    assert heavy <= set(files)
    assert set(order[:len(heavy)]) == heavy
    steps = [f for f in order if "cell_step" in files[f][1]]
    assert 11 <= len(steps) < len(heavy)
    pairs = 2 * min(len(steps), len(heavy) - len(steps))
    assert order[:pairs:2] == steps[:pairs // 2]
    assert not set(order[1:pairs:2]) & set(steps)
    shuffled = list(files.items())
    for seed in range(3):
        random.Random(seed).shuffle(shuffled)
        assert conftest.heavy_first(dict(shuffled)) == order

    def item(file, name, *fixtures, **module):
        return types.SimpleNamespace(
            nodeid=f"{file}::{name}", fixturenames=fixtures,
            module=types.SimpleNamespace(**module))
    items = [item("test_a.py", "one"), item("test_m.py", "two", "jax_cpu"),
             item("test_z.py", "b", FAMILY=1), item("test_m.py", "one"),
             item("test_z.py", "a", FAMILY=1),
             item("test_y.py", "step", "v5e", "cell_step")]
    conftest.pytest_collection_modifyitems(items)
    assert [i.nodeid for i in items] == [
        "test_y.py::step", "test_z.py::b", "test_z.py::a", "test_m.py::two",
        "test_m.py::one", "test_a.py::one"]
    config = types.SimpleNamespace(
        option=types.SimpleNamespace(loadscopereorder=True),
        addinivalue_line=lambda *a: None)
    conftest.pytest_configure(config)
    assert config.option.loadscopereorder is False


def test_a_cells_whole_step_has_one_builder():
    """tests/helpers/described_chip.py:CellStep is the one place under tests/
    that turns a cell of BENCHMARK.json into `make_train_step`'s step at the
    cell's real sizes, and it builds the step the chip runs: (a) no other
    function under tests/ both calls `make_train_step` and reads
    BENCHMARK.json or a file of benchmark/configs/ (the rehearsal's tiny
    configurations are another thing); (b) nobody tells the builder a
    memory limit: `CellStep` takes none, no family's row has one, and
    `memory.device_limit` is replaced in the builder alone; (c) every cell
    in LOWERED is read off a family's one whole step or lowered in
    tests/test_lowered_steps.py, and none twice."""
    import ast
    import glob
    import inspect
    import re

    tests = os.path.join(REPO, "tests")
    check = "test_the_cells_that_were_there_lower_to_the_same_step"
    builders, limits, steps, checked = [], [], [], []
    for path in sorted(glob.glob(os.path.join(tests, "**", "*.py"),
                                 recursive=True)):
        with open(path) as f:
            source = f.read()
        name = os.path.relpath(path, tests)
        if path == os.path.abspath(__file__):
            continue        # this case names what it looks for
        if re.search(r"""setattr\(\s*memory,\s*["']device_limit""", source):
            limits.append(name)
        for node in ast.walk(ast.parse(source)):
            if isinstance(node, ast.ImportFrom) \
                    and node.module == "helpers.described_chip":
                names = {alias.name for alias in node.names}
                steps += [name] * ("cell_step" in names)
                checked += [name] * (check in names)
            if not isinstance(node, ast.FunctionDef):
                continue
            body = ast.get_source_segment(source, node)
            if "make_train_step(" in body and re.search(
                    r"""BENCHMARK\.json|["']benchmark["'],\s*["']configs["']"""
                    r"""|benchmark/configs""", body):
                builders.append(f"{name}:{node.name}")
    assert builders == ["helpers/described_chip.py:__init__"], builders
    assert limits == ["helpers/described_chip.py"], limits

    sys.path.insert(0, tests)
    try:
        chip = importlib.import_module("helpers.described_chip")
        families = importlib.import_module("helpers.families")
        lowered = importlib.import_module("test_lowered_steps")
        rows = [importlib.import_module(file[:-3]).FAMILY
                for file in families.FILES.values() if file]
    finally:
        sys.path.remove(tests)
    assert list(inspect.signature(chip.CellStep).parameters) == [
        "cell", "devices", "axes"]
    for row in rows + [families.Family]:
        assert not [name for name in dir(row) if "limit" in name], row
    read = [row.workload for row in rows] + list(lowered.ON_THE_CPU)
    assert sorted(read) == sorted(lowered.LOWERED), read
    assert steps == checked and len(steps) == len(rows), (steps, checked)
    for row in rows:
        assert row.cases["lowered"][0].values == (row.workload,)
