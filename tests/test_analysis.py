"""graftlint (bigdl_tpu/analysis): fixture snippets per rule —
positive, suppressed, baseline-filtered — plus the real-tree gate and
the regression guard that the clock/atomic sites fixed in this PR stay
clean. Deliberately jax-free (the lint contract) and fast."""

import io
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from bigdl_tpu.analysis import core as lc
from bigdl_tpu.analysis import checks as lck

pytestmark = pytest.mark.core

REPO = os.path.dirname(lc.PACKAGE_DIR)


def lint(src: str, rel: str, rule=None):
    out = lc.lint_text(textwrap.dedent(src), rel)
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


# ---------------------------------------------------------------------------
# WCT001 — wall-clock ban
# ---------------------------------------------------------------------------

def test_wct001_fires_on_call_in_scope():
    fs = lint("""
        import time

        def f():
            return time.time()
    """, "bigdl_tpu/serving/foo.py", "WCT001")
    assert len(fs) == 1
    assert "time.time" in fs[0].message
    assert fs[0].line == 5


def test_wct001_default_arg_reference_is_allowed():
    # referencing the wall clock as a default *implementation* is the
    # documented escape hatch; only calls are banned
    fs = lint("""
        import time

        def f(clock=time.time):
            return clock()
    """, "bigdl_tpu/obs/foo.py", "WCT001")
    assert fs == []


def test_wct001_from_import_alias_is_caught():
    fs = lint("""
        from time import monotonic as mono

        def f():
            return mono()
    """, "bigdl_tpu/serving/foo.py", "WCT001")
    assert len(fs) == 1
    fs = lint("""
        from datetime import datetime as dt

        def f():
            return dt.now()
    """, "bigdl_tpu/serving/foo.py", "WCT001")
    assert len(fs) == 1


def test_wct001_out_of_scope_file_ignored():
    fs = lint("import time\nx = time.time()\n",
              "bigdl_tpu/convert/foo.py", "WCT001")
    assert fs == []


def test_wct001_covers_qcollectives():
    # ISSUE 17: the quantized-collective module runs inside jit traces
    # priced by the roofline/sim models — it joined the clock-injected
    # scope set, so a wall-clock call there must fire
    fs = lint("""
        import time

        def encode(x):
            t0 = time.time()
            return x, t0
    """, "bigdl_tpu/parallel/qcollectives.py", "WCT001")
    assert len(fs) == 1
    assert "time.time" in fs[0].message
    # siblings in parallel/ (other than health.py) stay out of scope
    assert lint("import time\nx = time.time()\n",
                "bigdl_tpu/parallel/ring.py", "WCT001") == []


def test_wct001_inline_suppression():
    fs = lint("""
        import time
        t = time.monotonic()  # graftlint: disable=WCT001
    """, "bigdl_tpu/serving/foo.py", "WCT001")
    assert fs == []


# ---------------------------------------------------------------------------
# ATW001 — non-atomic writes
# ---------------------------------------------------------------------------

def test_atw001_fires_on_write_mode():
    for mode in ("w", "wb", "w+"):
        fs = lint(f"f = open(p, {mode!r})\n", "bigdl_tpu/x.py", "ATW001")
        assert len(fs) == 1, mode


def test_atw001_read_and_append_are_fine():
    src = "a = open(p)\nb = open(p, 'rb')\nc = open(p, 'a')\n"
    assert lint(src, "bigdl_tpu/x.py", "ATW001") == []


def test_atw001_durability_is_the_exempt_protocol():
    src = "f = open(p, 'wb')\n"
    assert lint(src, "bigdl_tpu/utils/durability.py", "ATW001") == []
    assert len(lint(src, "bigdl_tpu/utils/other.py", "ATW001")) == 1


# ---------------------------------------------------------------------------
# FLT001 — fault-point validity (registries parsed from the real tree)
# ---------------------------------------------------------------------------

def test_flt001_declared_point_ok_undeclared_fires():
    ok = lint("x = self._faults.fire('alloc_page')\n",
              "bigdl_tpu/serving/foo.py", "FLT001")
    assert ok == []
    bad = lint("x = self._faults.fire('totally_bogus')\n",
               "bigdl_tpu/serving/foo.py", "FLT001")
    assert len(bad) == 1
    assert "totally_bogus" in bad[0].message


def test_flt001_scoped_per_registry():
    # rank_drop is a *train* point: valid in train/, a typo in serving/
    src = "inj.arm('rank_drop')\n"
    assert lint(src, "bigdl_tpu/train/foo.py", "FLT001") == []
    assert len(lint(src, "bigdl_tpu/serving/foo.py", "FLT001")) == 1


def test_flt001_covers_qcollectives():
    # parallel/ maps to the train fault registry: a bogus point in the
    # new collectives module is a typo, a declared train point is fine
    bad = lint("inj.fire('bogus_point')\n",
               "bigdl_tpu/parallel/qcollectives.py", "FLT001")
    assert len(bad) == 1
    assert "bogus_point" in bad[0].message
    assert lint("inj.arm('rank_drop')\n",
                "bigdl_tpu/parallel/qcollectives.py", "FLT001") == []


def test_flt001_dynamic_point_string_is_skipped():
    assert lint("inj.fire(point)\n",
                "bigdl_tpu/serving/foo.py", "FLT001") == []


# ---------------------------------------------------------------------------
# LCK001 — lock discipline
# ---------------------------------------------------------------------------

_LOCKED_CLASS = """
    import threading

    class Eng:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0  # guarded-by: _lock

        def good(self):
            with self._lock:
                self.count += 1

        def bad(self):
            return self.count
"""


def test_lck001_fires_outside_with_block():
    fs = lint(_LOCKED_CLASS, "bigdl_tpu/serving/foo.py", "LCK001")
    assert len(fs) == 1
    assert "self.count" in fs[0].message
    assert "bad" not in fs[0].hint  # message names the attr, not the fn
    assert fs[0].line == _LOCKED_CLASS.splitlines().index(
        "            return self.count") + 1


def test_lck001_constructor_is_exempt():
    fs = lint("""
        class Eng:
            def __init__(self):
                self.n = 0  # guarded-by: _lock
                self.n += 1
    """, "bigdl_tpu/serving/foo.py", "LCK001")
    assert fs == []


def test_lck001_comment_above_form_and_no_leak_to_next_line():
    fs = lint("""
        class Eng:
            def __init__(self):
                # guarded-by: _lock
                self.a = 0
                self.b = 0

            def f(self):
                return self.b  # unguarded attr: fine

            def g(self):
                return self.a  # violation
    """, "bigdl_tpu/serving/foo.py", "LCK001")
    assert len(fs) == 1 and "self.a" in fs[0].message


def test_lck001_nested_function_holds_nothing():
    # a closure defined under the lock may run after release
    fs = lint("""
        class Eng:
            def __init__(self):
                self.n = 0  # guarded-by: _lock

            def f(self):
                with self._lock:
                    def cb():
                        return self.n
                    return cb
    """, "bigdl_tpu/serving/foo.py", "LCK001")
    assert len(fs) == 1


# ---------------------------------------------------------------------------
# MET001 — static metrics drift
# ---------------------------------------------------------------------------

def test_met001_real_metrics_module_is_reconciled():
    path = os.path.join(lc.PACKAGE_DIR, "serving", "metrics.py")
    with open(path, encoding="utf-8") as f:
        fs = lint(f.read(), "bigdl_tpu/serving/metrics.py", "MET001")
    assert fs == [], [f.format() for f in fs]


def test_met001_synthetic_two_way_drift():
    fs = lint("""
        _PROCESS_FAMILIES = ("bigdl_tpu_registered_only_total",)

        def render():
            return "# TYPE bigdl_tpu_rendered_only_total counter"
    """, "bigdl_tpu/serving/metrics.py", "MET001")
    msgs = " | ".join(f.message for f in fs)
    assert len(fs) == 2
    assert "bigdl_tpu_rendered_only_total" in msgs  # unregistered
    assert "bigdl_tpu_registered_only_total" in msgs  # never rendered


def test_met001_only_applies_to_metrics_py():
    fs = lint('x = "# TYPE bigdl_tpu_whatever_total counter"\n',
              "bigdl_tpu/serving/other.py", "MET001")
    assert fs == []


# ---------------------------------------------------------------------------
# DON001 — donation hazard
# ---------------------------------------------------------------------------

def test_don001_read_after_donation_fires():
    fs = lint("""
        import jax

        def f(step, x):
            g = jax.jit(step, donate_argnums=(0,))
            y = g(x)
            return x + y
    """, "bigdl_tpu/ops/foo.py", "DON001")
    assert len(fs) == 1
    assert "'x'" in fs[0].message


def test_don001_rebind_over_donated_name_is_clean():
    fs = lint("""
        import jax

        def f(step, x):
            g = jax.jit(step, donate_argnums=(0,))
            x = g(x)
            return x
    """, "bigdl_tpu/ops/foo.py", "DON001")
    assert fs == []


def test_don001_donate_argnames_keyword_call():
    fs = lint("""
        import jax

        def f(step, cache, tok):
            g = jax.jit(step, donate_argnames=("cache",))
            out = g(tok, cache=cache)
            return cache.pos
    """, "bigdl_tpu/ops/foo.py", "DON001")
    assert len(fs) == 1 and "'cache'" in fs[0].message


def test_don001_nested_function_scope_is_separate():
    # a nested def's same-named parameter is a different variable; it
    # must neither fire nor mask (review finding)
    fs = lint("""
        import jax

        def f(step, x):
            g = jax.jit(step, donate_argnums=(0,))
            y = g(x)

            def h(x):
                return x + 1

            return y
    """, "bigdl_tpu/ops/foo.py", "DON001")
    assert fs == []
    # ...and a Store inside a nested def must not mask an outer read
    fs = lint("""
        import jax

        def f(step, x):
            g = jax.jit(step, donate_argnums=(0,))
            y = g(x)

            def h():
                x = 0
                return x

            return x + y
    """, "bigdl_tpu/ops/foo.py", "DON001")
    assert len(fs) == 1


def test_don001_non_donating_jit_ignored():
    fs = lint("""
        import jax

        def f(step, x):
            g = jax.jit(step)
            y = g(x)
            return x + y
    """, "bigdl_tpu/ops/foo.py", "DON001")
    assert fs == []


# ---------------------------------------------------------------------------
# CRC001 — journal-line discipline
# ---------------------------------------------------------------------------

def test_crc001_bare_jsonl_write_fires():
    fs = lint("""
        import json

        def log(f, rec):
            f.write(json.dumps(rec) + "\\n")
    """, "bigdl_tpu/serving/foo.py", "CRC001")
    assert len(fs) == 1


def test_crc001_crc_line_wrapped_is_clean():
    fs = lint("""
        import json
        from bigdl_tpu.serving.journal import crc_line

        def log(f, rec):
            f.write(crc_line(json.dumps(rec)) + "\\n")
    """, "bigdl_tpu/serving/foo.py", "CRC001")
    assert fs == []


def test_crc001_wire_protocols_and_documents_exempt():
    # SSE framing (\\n\\n), NUL-delimited streams, and whole-document
    # JSON are different contracts, not journal lines
    src = """
        import json

        def sse(w, evt):
            w.write(f"data: {json.dumps(evt)}\\n\\n".encode())

        def fastchat(w, chunk):
            w.write(json.dumps(chunk).encode() + b"\\0")

        def config(f, meta):
            f.write(json.dumps(meta, indent=1).encode())
    """
    assert lint(src, "bigdl_tpu/serving/foo.py", "CRC001") == []


# ---------------------------------------------------------------------------
# suppression / baseline machinery
# ---------------------------------------------------------------------------

def test_suppression_on_line_above():
    fs = lint("""
        import time
        # graftlint: disable=WCT001
        t = time.time()
    """, "bigdl_tpu/serving/foo.py", "WCT001")
    assert fs == []


def test_baseline_filters_on_rule_path_code(tmp_path):
    findings = lint("import time\nt = time.time()\n",
                    "bigdl_tpu/serving/foo.py", "WCT001")
    assert len(findings) == 1
    bl = [{"rule": "WCT001", "path": "bigdl_tpu/serving/foo.py",
           "code": "t = time.time()", "justification": "fixture"}]
    new, old = lc.apply_baseline(findings, bl)
    assert new == [] and len(old) == 1
    # a different offending line is NOT absorbed
    other = lint("import time\nu = time.time()\n",
                 "bigdl_tpu/serving/foo.py", "WCT001")
    new2, _ = lc.apply_baseline(other, bl)
    assert len(new2) == 1


def test_baseline_entries_require_justification(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"findings": [
        {"rule": "WCT001", "path": "x.py", "code": "t = time.time()"}
    ]}))
    with pytest.raises(ValueError, match="justification"):
        lc.load_baseline(str(p))


def test_write_baseline_refused_under_filters_and_keeps_justifications(
        tmp_path):
    # a filtered scan must never be written as THE baseline (it would
    # drop every grandfathered entry outside the slice) ...
    assert lc.run(paths=["bigdl_tpu/serving"], write_baseline_path="x",
                  out=open(os.devnull, "w")) == 2
    assert lc.run(rules=["WCT001"], write_baseline_path="x",
                  out=open(os.devnull, "w")) == 2
    # ... and a full rewrite carries surviving entries' justifications
    f = lc.Finding("WCT001", "a.py", 3, "m", code="t = time.time()")
    prev = [{"rule": "WCT001", "path": "a.py",
             "code": "t = time.time()", "justification": "kept reason"}]
    p = tmp_path / "bl.json"
    lc.write_baseline([f], str(p), previous=prev)
    assert lc.load_baseline(str(p))[0]["justification"] == "kept reason"


def test_shipped_baseline_loads_and_is_empty_or_justified():
    entries = lc.load_baseline(lc.DEFAULT_BASELINE)
    for e in entries:  # load_baseline enforces justification; re-assert
        assert e.get("justification")


# ---------------------------------------------------------------------------
# the real gate
# ---------------------------------------------------------------------------

def test_real_tree_has_zero_non_baselined_findings():
    t0 = time.monotonic()
    findings = lc.lint_paths()
    new, _ = lc.apply_baseline(findings, lc.load_baseline(
        lc.DEFAULT_BASELINE))
    assert new == [], "\n".join(f.format() for f in new)
    assert time.monotonic() - t0 < 10.0, "lint must stay under 10 s"


def test_fixed_clock_and_atomic_sites_stay_clean():
    """Regression guard for THIS PR's cleanup: the api_server/engine
    wall-clock sites and the tracing/report bare writes must never
    reappear (they are also covered by the tree-wide gate; this names
    the exact files so a regression reads as what it is)."""
    fixed = [
        "bigdl_tpu/serving/api_server.py",
        "bigdl_tpu/serving/engine.py",
        "bigdl_tpu/obs/tracing.py",
        "bigdl_tpu/obs/profiler.py",
        "bigdl_tpu/benchmark/report.py",
        "bigdl_tpu/parallel/health.py",
        "bigdl_tpu/train/supervisor.py",
    ]
    paths = [os.path.join(REPO, p) for p in fixed]
    findings = [f for f in lc.lint_paths(paths)
                if f.rule in ("WCT001", "ATW001")]
    assert findings == [], "\n".join(f.format() for f in findings)


def test_lint_cli_runs_without_importing_jax():
    """The ci.sh --lint contract, end to end: a fresh interpreter runs
    the full gate and jax never enters sys.modules."""
    code = (
        "import sys\n"
        "from bigdl_tpu.analysis import run\n"
        "rc = run()\n"
        "assert 'jax' not in sys.modules, 'graftlint imported jax'\n"
        "sys.exit(rc)\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_parse_error_is_a_finding_not_a_crash():
    fs = lc.lint_text("def broken(:\n", "bigdl_tpu/x.py")
    assert len(fs) == 1 and fs[0].rule == "PARSE"


# ---------------------------------------------------------------------------
# PAGE0xx — interprocedural page-ref liveness (analysis/flow.py)
# ---------------------------------------------------------------------------

def test_page001_leak_on_early_return():
    fs = lint("""
        class Holder:
            def grab(self, want):
                pg = self.pool.alloc()
                if want:
                    return True  # leaks pg
                self.pool.decref(pg)
                return False
    """, "bigdl_tpu/serving/pagefix.py", "PAGE001")
    assert len(fs) == 1
    assert "pg" in fs[0].message and "return" in fs[0].message


def test_page001_none_refined_rollback_and_transfer_are_clean():
    # the engine's _admit_paged shape: incref loop, alloc loop with
    # full rollback on a dry pool, then ownership transfer into the
    # slot table — no finding on any path
    fs = lint("""
        class Holder:
            def admit(self, shared, need, slot):
                for pg in shared:
                    self.pool.incref(pg)
                fresh = []
                for _ in range(need):
                    pg = self.pool.alloc()
                    if pg is None:
                        for q in fresh:
                            self.pool.decref(q)
                        for q in shared:
                            self.pool.decref(q)
                        return False
                    fresh.append(pg)
                table = shared + fresh
                self._slots[slot] = table
                return True
    """, "bigdl_tpu/serving/pagefix.py")
    assert [f for f in fs if f.rule.startswith("PAGE")] == []


def test_page001_return_of_ref_is_a_transfer_not_a_leak():
    fs = lint("""
        class Holder:
            def take(self):
                pg = self.pool.alloc()
                return pg
    """, "bigdl_tpu/serving/pagefix.py", "PAGE001")
    assert fs == []


def test_page002_may_raise_call_with_live_refs_fires():
    fs = lint("""
        class Pager:
            def page_in(self, n, flat):
                pages = []
                for _ in range(n):
                    pg = self.pool.alloc()
                    if pg is None:
                        for p in pages:
                            self.pool.decref(p)
                        return False
                    pages.append(pg)
                self.store.write(pages, flat)  # may raise; pages leak
                self._res["x"] = pages
                return True
    """, "bigdl_tpu/serving/pagefix.py", "PAGE002")
    assert len(fs) == 1
    assert "pages" in fs[0].message


def test_page002_try_except_rollback_is_clean():
    fs = lint("""
        class Pager:
            def page_in(self, n, flat):
                pages = []
                for _ in range(n):
                    pg = self.pool.alloc()
                    if pg is None:
                        return False
                    pages.append(pg)
                try:
                    self.store.write(pages, flat)
                except Exception:
                    for p in pages:
                        self.pool.decref(p)
                    raise
                self._res["x"] = pages
                return True
    """, "bigdl_tpu/serving/pagefix.py", "PAGE002")
    assert fs == []


def test_page002_suppression_comment_silences_the_site():
    fs = lint("""
        class Pager:
            def page_in(self, n, flat):
                pg = self.pool.alloc()
                # graftlint: disable=PAGE002
                self.store.write([pg], flat)
                self._res["x"] = [pg]
                self.pool.decref(pg)
    """, "bigdl_tpu/serving/pagefix.py", "PAGE002")
    assert fs == []


def test_page_findings_are_baselinable_like_any_other():
    findings = lint("""
        class Holder:
            def grab(self):
                pg = self.pool.alloc()
                return True
    """, "bigdl_tpu/serving/pagefix.py", "PAGE001")
    assert len(findings) == 1
    bl = [{"rule": "PAGE001", "path": "bigdl_tpu/serving/pagefix.py",
           "code": findings[0].code, "justification": "fixture"}]
    new, old = lc.apply_baseline(findings, bl)
    assert new == [] and len(old) == 1


def test_page002_regression_the_adapter_pager_bug_shape():
    """The exact pre-fix AdapterPager.ensure shape: allocate the page
    run, then store.write with no try — the refs strand if the device
    scatter raises. This PR fixed the real site (serving/adapters.py);
    this fixture pins the checker's ability to catch the class."""
    fs = lint("""
        class Pager:
            def ensure(self, entry, rid):
                flat = self._flatten(entry)
                pages = []
                for _ in range(self.store.n_for(flat.size)):
                    pg = self._alloc()
                    if pg is None:
                        for p in pages:
                            self._pool.decref(p)
                        return False
                    pages.append(pg)
                self.store.write(pages, flat)
                rec = _PagedAdapter(entry.name, pages, [], 0)
                self._res[entry.name] = rec
                return True
    """, "bigdl_tpu/serving/pagefix.py", "PAGE002")
    assert len(fs) == 1 and "write" in fs[0].code


def test_page_real_adapter_and_engine_paths_are_clean():
    paths = [os.path.join(REPO, p) for p in (
        "bigdl_tpu/serving/adapters.py",
        "bigdl_tpu/serving/engine.py",
        "bigdl_tpu/serving/pages.py",
        "bigdl_tpu/serving/radix.py",
        "bigdl_tpu/kvpaged.py",
    )]
    fs = [f for f in lc.lint_paths(paths) if f.rule.startswith("PAGE")]
    assert fs == [], "\n".join(f.format() for f in fs)


# ---------------------------------------------------------------------------
# LCK1xx — lock-order cycles + blocking under hot locks
# ---------------------------------------------------------------------------

def test_lck101_opposite_order_is_a_cycle_with_witnesses():
    fs = lint("""
        import threading

        class Box:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def ab(self):
                with self._a:
                    with self._b:
                        pass

            def ba(self):
                with self._b:
                    with self._a:
                        pass
    """, "bigdl_tpu/serving/lockfix.py", "LCK101")
    assert len(fs) >= 1
    msg = fs[0].message
    assert "cycle" in msg and "Box._a" in msg and "Box._b" in msg
    # both witness paths are named in the message
    assert msg.count("acquires") >= 2


def test_lck101_cross_function_cycle_through_the_call_graph():
    fs = lint("""
        import threading

        class Box:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def outer(self):
                with self._a:
                    self.helper()

            def helper(self):
                with self._b:
                    pass

            def other(self):
                with self._b:
                    with self._a:
                        pass
    """, "bigdl_tpu/serving/lockfix.py", "LCK101")
    assert len(fs) >= 1
    assert "cycle" in fs[0].message


def test_lck101_consistent_order_is_clean():
    fs = lint("""
        import threading

        class Box:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def m1(self):
                with self._a:
                    with self._b:
                        pass

            def m2(self):
                with self._a:
                    with self._b:
                        pass
    """, "bigdl_tpu/serving/lockfix.py", "LCK101")
    assert fs == []


def test_lck101_rlock_reentry_is_allowed_plain_lock_is_not():
    src = """
        import threading

        class Reg:
            def __init__(self):
                self._lock = threading.{kind}()

            def get(self):
                with self._lock:
                    return 1

            def acquire(self):
                with self._lock:
                    return self.get()
    """
    assert lint(src.format(kind="RLock"),
                "bigdl_tpu/serving/lockfix.py", "LCK101") == []
    fs = lint(src.format(kind="Lock"),
              "bigdl_tpu/serving/lockfix.py", "LCK101")
    assert len(fs) == 1 and "re-acquisition" in fs[0].message


def test_lck102_blocking_call_under_hot_lock_fires():
    fs = lint("""
        import threading

        class Eng:
            def __init__(self):
                self._stat_lock = threading.Lock()

            def scrape(self):
                with self._stat_lock:
                    self.f.flush()
    """, "bigdl_tpu/serving/lockfix.py", "LCK102")
    assert len(fs) == 1
    assert "flush" in fs[0].message and "_stat_lock" in fs[0].message


def test_lck102_blocking_after_release_is_clean():
    fs = lint("""
        import threading

        class Eng:
            def __init__(self):
                self._stat_lock = threading.Lock()

            def scrape(self):
                with self._stat_lock:
                    snap = dict(self.stats)
                self.f.flush()
                return snap
    """, "bigdl_tpu/serving/lockfix.py", "LCK102")
    assert fs == []


def test_lck102_transitively_blocking_callee_fires_at_the_lock_frame():
    fs = lint("""
        import threading

        class Eng:
            def __init__(self):
                self._admission_lock = threading.Lock()

            def _persist(self):
                self.f.flush()

            def submit(self, req):
                with self._admission_lock:
                    self._persist()
    """, "bigdl_tpu/serving/lockfix.py", "LCK102")
    assert len(fs) == 1
    # anchored at submit's call site (the frame holding the lock),
    # not inside _persist
    assert "_persist" in fs[0].message


def test_lck102_suppression_comment_silences_the_site():
    fs = lint("""
        import threading

        class Eng:
            def __init__(self):
                self._stat_lock = threading.Lock()

            def scrape(self):
                with self._stat_lock:
                    # graftlint: disable=LCK102
                    self.f.flush()
    """, "bigdl_tpu/serving/lockfix.py", "LCK102")
    assert fs == []


def test_lck_real_tree_only_the_baselined_submit_journal_remains():
    """The shipped tree's only LCK finding is the justified
    record_submit-under-_admission_lock baseline entry (journal order
    must match queue order; see baseline.json)."""
    findings = [f for f in lc.lint_paths() if f.rule.startswith("LCK1")]
    new, old = lc.apply_baseline(
        findings, lc.load_baseline(lc.DEFAULT_BASELINE))
    assert new == [], "\n".join(f.format() for f in new)
    assert len(old) == 1 and "record_submit" in old[0].code


# ---------------------------------------------------------------------------
# DSP0xx — kernel-dispatch consistency (registry <-> tables <-> budgets)
# ---------------------------------------------------------------------------

def test_dsp001_missing_and_unknown_gemv_entries():
    # overlay of ops/linear.py: the registry (real quant/qtypes.py) has
    # many non-dense qtypes; this table covers one and invents one
    fs = lint("""
        _QGEMV_QTYPES = {
            "sym_int4": 64,
            "bogus_q9": 64,
        }
    """, "bigdl_tpu/ops/linear.py", "DSP001")
    missing = [f for f in fs if "has no _QGEMV_QTYPES entry" in f.message]
    unknown = [f for f in fs if "bogus_q9" in f.message]
    assert any("asym_int4" in f.message for f in missing)
    assert len(unknown) == 1 and "not registered" in unknown[0].message


@pytest.mark.parametrize("rule", ["DSP001", "DSP003"])
def test_dsp001_real_linear_table_is_complete(rule):
    """Every registered format has its k_multiple (DSP001), and each is a
    multiple of the format's block and superblock (DSP003)."""
    fs = [f for f in lc.lint_paths(
        [os.path.join(REPO, "bigdl_tpu/ops/linear.py")])
        if f.rule == rule]
    assert fs == [], "\n".join(f.format() for f in fs)


def test_dsp002_phantom_pallas_import():
    fs = lint("""
        from bigdl_tpu.ops.pallas import use_pallas, totally_bogus_kernel
    """, "bigdl_tpu/ops/foo.py", "DSP002")
    assert len(fs) == 1 and "totally_bogus_kernel" in fs[0].message
    assert lint("""
        from bigdl_tpu.ops.pallas import use_pallas
    """, "bigdl_tpu/ops/foo.py", "DSP002") == []


def test_dsp003_k_multiple_must_respect_block_size():
    # sym_int4's block_size is 32; a k_multiple of 48 splits blocks
    fs = lint("""
        _QGEMV_QTYPES = {
            "sym_int4": 48,
        }
    """, "bigdl_tpu/ops/linear.py", "DSP003")
    assert len(fs) == 1 and "48" in fs[0].message \
        and "block" in fs[0].message
    assert lint("""
        _QGEMV_QTYPES = {
            "sym_int4": 64,
        }
    """, "bigdl_tpu/ops/linear.py", "DSP003") == []


def test_dsp003_spec_for_must_cover_every_storage_or_default():
    gap = lint("""
        def spec_for(spec):
            if spec.storage == "packed_u8":
                return 1
    """, "bigdl_tpu/ops/pallas/qdecode.py", "DSP003")
    assert any("packed_planes" in f.message for f in gap)
    assert lint("""
        def spec_for(spec):
            if spec.storage == "packed_u8":
                return 1
            raise ValueError(spec.storage)
    """, "bigdl_tpu/ops/pallas/qdecode.py", "DSP003") == []


def test_dsp006_inline_kv_astype_fires():
    fs = lint("""
        def _kernel(q_ref, k_ref, v_ref, o_ref):
            q = q_ref[0, 0].astype(jnp.float32)
            k = k_ref[0, 0].astype(jnp.float32)
            v = qdecode.decode_kv(v_ref[0, 0])
    """, "bigdl_tpu/ops/pallas/flash_attention.py", "DSP006")
    assert len(fs) == 1 and "k_ref" in fs[0].message
    # q_ref is not a KV tile; decode_kv'd v is the blessed path


def test_dsp006_direct_decode_values_in_epilogue_fires():
    fs = lint("""
        def _kernel(k_ref, o_ref):
            k = decode_values(k_ref[0, 0], ("e5m2",))
    """, "bigdl_tpu/ops/pallas/paged_attention.py", "DSP006")
    assert any("decode_values" in f.message for f in fs)


def test_dsp006_missing_decode_kv_is_a_regression():
    fs = lint("""
        def _kernel(k_ref, v_ref, o_ref):
            k = k_ref[0, 0] * 1.0
    """, "bigdl_tpu/ops/pallas/flash_backward.py", "DSP006")
    assert len(fs) == 1 and "regressed" in fs[0].message


def test_dsp006_scope_is_the_attention_epilogues_only():
    assert lint("""
        def _kernel(k_ref, o_ref):
            k = k_ref[0, 0].astype(jnp.float32)
    """, "bigdl_tpu/ops/pallas/qmatmul.py", "DSP006") == []


def test_dsp006_real_attention_files_clean():
    paths = [os.path.join(REPO, "bigdl_tpu/ops/pallas", n) for n in
             ("flash_attention.py", "paged_attention.py",
              "flash_backward.py")]
    fs = [f for f in lc.lint_paths(paths) if f.rule == "DSP006"]
    assert fs == [], "\n".join(f.format() for f in fs)


def test_dsp004_restated_budget_literal_in_ops_fires():
    # 5 MiB == VMEM_BUDGET // 2 (tiling.py): the drift this rule was
    # written for, in `ops/linear`'s VMEM guard
    fs = lint("""
        CAP = 5 * 1024 * 1024
    """, "bigdl_tpu/ops/foo.py", "DSP004")
    assert len(fs) == 1 and "VMEM_BUDGET // 2" in fs[0].message
    # an unrelated MiB value is fine, and non-ops files are out of scope
    assert lint("CAP = 7 * 1024 * 1024\n",
                "bigdl_tpu/ops/foo.py", "DSP004") == []
    assert lint("CAP = 5 * 1024 * 1024\n",
                "bigdl_tpu/quant/foo.py", "DSP004") == []


def test_dsp005_lora_cap_must_leave_base_kernel_headroom():
    fs = lint("""
        VMEM_BUDGET = 10 * 1024 * 1024
        LORA_VMEM_CAP = 6 * 1024 * 1024
    """, "bigdl_tpu/ops/pallas/tiling.py", "DSP005")
    assert len(fs) == 1 and "LORA_VMEM_CAP" in fs[0].message
    # anchored at the offending constant's own assignment line
    assert fs[0].code.startswith("LORA_VMEM_CAP")
    assert lint("""
        VMEM_BUDGET = 10 * 1024 * 1024
        LORA_VMEM_CAP = 4 * 1024 * 1024
    """, "bigdl_tpu/ops/pallas/tiling.py", "DSP005") == []


def test_dsp005_vmem_ceiling():
    fs = lint("""
        VMEM_BUDGET = 24 * 1024 * 1024
    """, "bigdl_tpu/ops/pallas/tiling.py", "DSP005")
    assert len(fs) == 1 and "16 MiB" in fs[0].message


@pytest.mark.parametrize("src,word", [
    ("VMEM_LIMIT_BYTES = 32 * 1024 * 1024\n"
     "WORDS_VMEM_BYTES = 28 * 1024 * 1024", "WORDS_VMEM_BYTES"),
    ("MOSAIC_LANES = 128\nWORD_ROWS = 4\nWORD_BLOCK_O = 256",
     "WORD_BLOCK_O"),
], ids=["vmem", "tile"])
def test_dsp005_word_path_policy(src, word):
    """The word path's constants (ISSUE 32): its tile transposes in whole
    128-lane pieces and leaves the chunk loop a quarter of the limit."""
    fs = lint(src, "bigdl_tpu/ops/pallas/tiling.py", "DSP005")
    assert len(fs) == 1 and word in fs[0].message
    assert fs[0].code.startswith(word)
    good = {"WORDS_VMEM_BYTES": src.replace("28", "20"),
            "WORD_BLOCK_O": src.replace("256", "512")}[word]
    assert lint(good, "bigdl_tpu/ops/pallas/tiling.py", "DSP005") == []


def test_dsp_suppression_comment_works():
    assert lint("""
        # graftlint: disable=DSP004
        CAP = 5 * 1024 * 1024
    """, "bigdl_tpu/ops/foo.py", "DSP004") == []


# ---------------------------------------------------------------------------
# Baseline hygiene (BASE001 + --update-baseline) and output formats
# ---------------------------------------------------------------------------

def test_stale_baseline_entry_is_an_error_on_full_scans(tmp_path):
    bl = tmp_path / "baseline.json"
    stale = {"rule": "WCT001", "path": "bigdl_tpu/serving/gone.py",
             "code": "t = time.time()", "justification": "long fixed"}
    entries = lc.load_baseline(lc.DEFAULT_BASELINE) + [stale]
    bl.write_text(json.dumps({"findings": entries}))
    buf = io.StringIO()
    rc = lc.run(baseline_path=str(bl), out=buf)
    assert rc == 1
    assert "BASE001" in buf.getvalue()
    assert "stale baseline entry" in buf.getvalue()
    # stale_baseline_entries is the primitive behind it
    fs = lc.stale_baseline_entries([stale], [])
    assert len(fs) == 1 and fs[0].rule == "BASE001"


def test_update_baseline_drops_stale_and_keeps_justifications(tmp_path):
    bl = tmp_path / "baseline.json"
    stale = {"rule": "WCT001", "path": "bigdl_tpu/serving/gone.py",
             "code": "t = time.time()", "justification": "long fixed"}
    entries = lc.load_baseline(lc.DEFAULT_BASELINE) + [stale]
    bl.write_text(json.dumps({"findings": entries}))
    buf = io.StringIO()
    rc = lc.run(baseline_path=str(bl), update_baseline=True, out=buf)
    assert rc == 0
    assert "1 stale dropped" in buf.getvalue()
    rewritten = lc.load_baseline(str(bl))
    assert all(e["path"] != "bigdl_tpu/serving/gone.py" for e in rewritten)
    kept = [e for e in rewritten if e["rule"] == "LCK102"]
    assert len(kept) == 1 and "journal order" in kept[0]["justification"]


def test_update_baseline_refused_under_filters(tmp_path):
    buf = io.StringIO()
    rc = lc.run(rules=["WCT001"], update_baseline=True, out=buf)
    assert rc == 2 and "full, unfiltered scan" in buf.getvalue()


def _violation_dir(tmp_path):
    d = tmp_path / "bigdl_tpu" / "serving"
    d.mkdir(parents=True)
    (d / "vio.py").write_text("import time\nt = time.time()\n")
    bl = tmp_path / "empty.json"
    bl.write_text('{"findings": []}')
    return str(tmp_path / "bigdl_tpu"), str(bl)


def test_format_json_is_machine_parseable(tmp_path):
    target, bl = _violation_dir(tmp_path)
    buf = io.StringIO()
    rc = lc.run(paths=[target], baseline_path=bl, fmt="json", out=buf)
    assert rc == 1
    doc = json.loads(buf.getvalue())
    assert doc["baselined"] == 0
    assert [f["rule"] for f in doc["findings"]] == ["WCT001"]
    assert doc["findings"][0]["path"].endswith("serving/vio.py")
    assert doc["findings"][0]["line"] == 2


def test_format_github_emits_error_annotations(tmp_path):
    target, bl = _violation_dir(tmp_path)
    buf = io.StringIO()
    rc = lc.run(paths=[target], baseline_path=bl, fmt="github", out=buf)
    assert rc == 1
    line = [l for l in buf.getvalue().splitlines()
            if l.startswith("::error ")][0]
    assert "file=" in line and ",line=2," in line \
        and "title=graftlint WCT001" in line


def test_format_unknown_is_a_usage_error():
    buf = io.StringIO()
    assert lc.run(fmt="yaml", out=buf) == 2
    assert "unknown format" in buf.getvalue()


def test_shipped_baseline_has_no_stale_entries():
    findings = lc.lint_paths()
    stale = lc.stale_baseline_entries(
        lc.load_baseline(lc.DEFAULT_BASELINE), findings)
    assert stale == [], "\n".join(f.format() for f in stale)
