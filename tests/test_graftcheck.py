"""graftcheck static-analysis tests: every rule has a seeded-violation
fixture it detects AND a clean twin it passes; the real tree scans
clean; the waiver/baseline machinery round-trips; and the runtime
lock-order sanitizer detects a provoked A->B / B->A inversion.

The fixtures are the rules' contract: a rule that silently stopped
firing on its own triggering shape is worse than no rule.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.graftcheck import core  # noqa: E402
from tools.graftcheck.passes import flag_hygiene, stat_catalog  # noqa: E402


def run_on(tmp_path, source: str, rules, baseline: str = None):
    """Write one fixture module, run the selected passes on it, and
    return the violations list."""
    mod = tmp_path / "fixture.py"
    mod.write_text(textwrap.dedent(source))
    bl = None
    if baseline is not None:
        blf = tmp_path / "baseline.txt"
        blf.write_text(textwrap.dedent(baseline))
        bl = str(blf)
    report = core.run(roots=[str(mod)], rule_filter=rules,
                      baseline_path=bl)
    return report


def rules_of(report):
    return sorted({v.rule for v in report.violations})


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

LOCK_BARE = """
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self._queue = []
            self._draining = False

        def start(self):
            threading.Thread(target=self.worker).start()

        def worker(self):
            with self._lock:
                self._queue.append(1)
                self._draining = True

        def stats(self):
            return {"depth": len(self._queue),
                    "draining": self._draining}
"""

LOCK_BARE_CLEAN = """
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self._queue = []
            self._draining = False

        def start(self):
            threading.Thread(target=self.worker).start()

        def worker(self):
            with self._lock:
                self._queue.append(1)
                self._draining = True

        def stats(self):
            with self._lock:
                return {"depth": len(self._queue),
                        "draining": self._draining}

        def _drain_locked(self):
            # *_locked convention: the caller holds self._lock
            self._queue.clear()
            return self._draining
"""


def test_lock_bare_access_detected_and_clean_twin(tmp_path):
    r = run_on(tmp_path, LOCK_BARE, ["lock-discipline"])
    assert "lock-bare-access" in rules_of(r)
    keys = {v.key for v in r.violations}
    assert any("Engine.stats._queue" in k for k in keys)
    assert any("Engine.stats._draining" in k for k in keys)

    r = run_on(tmp_path, LOCK_BARE_CLEAN, ["lock-discipline"])
    assert r.violations == []


def test_lock_bare_access_wrong_lock_is_not_protection(tmp_path):
    """Holding an UNRELATED lock must not silence the race: lock
    identity matters, not lock count."""
    src = LOCK_BARE.replace(
        'def stats(self):\n'
        '            return {"depth": len(self._queue),\n'
        '                    "draining": self._draining}',
        'def stats(self):\n'
        '            with self._other:\n'
        '                return {"depth": len(self._queue),\n'
        '                        "draining": self._draining}')
    src = src.replace(
        "self._lock = threading.Lock()",
        "self._lock = threading.Lock()\n"
        "            self._other = threading.Lock()")
    r = run_on(tmp_path, src, ["lock-discipline"])
    msgs = [v for v in r.violations if v.rule == "lock-bare-access"]
    assert any("holding only" in v.message and "_other" in v.message
               for v in msgs), [v.message for v in msgs]


def test_lock_bare_access_requires_threaded_class(tmp_path):
    # same shape but no Thread anywhere: single-threaded class, no
    # finding (and no marker opt-in)
    src = LOCK_BARE.replace(
        "threading.Thread(target=self.worker).start()", "self.worker()")
    r = run_on(tmp_path, src, ["lock-discipline"])
    assert r.violations == []


LOCK_ORDER = """
    import threading

    class TwoLocks:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def forward(self):
            with self._a:
                with self._b:
                    pass

        def backward(self):
            with self._b:
                with self._a:
                    pass
"""

LOCK_ORDER_CLEAN = """
    import threading

    class TwoLocks:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def forward(self):
            with self._a:
                with self._b:
                    pass

        def also_forward(self):
            with self._a:
                with self._b:
                    pass
"""


def test_lock_order_cycle_detected_and_clean_twin(tmp_path):
    r = run_on(tmp_path, LOCK_ORDER, ["lock-discipline"])
    assert rules_of(r) == ["lock-order"]
    assert {v.key for v in r.violations} == \
        {"TwoLocks._a->TwoLocks._b", "TwoLocks._b->TwoLocks._a"}

    r = run_on(tmp_path, LOCK_ORDER_CLEAN, ["lock-discipline"])
    assert r.violations == []


def test_lock_order_interprocedural_and_self_nest(tmp_path):
    src = """
    import threading

    class Indirect:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def holder(self):
            with self._a:
                self.helper()

        def helper(self):
            with self._b:
                pass

        def reverse(self):
            with self._b:
                with self._a:
                    pass

    class SelfNest:
        def __init__(self):
            self._lock = threading.Lock()

        def oops(self):
            with self._lock:
                with self._lock:
                    pass
    """
    r = run_on(tmp_path, src, ["lock-discipline"])
    keys = {v.key for v in r.violations}
    # the A->B edge exists only through the helper() call
    assert "Indirect._a->Indirect._b" in keys
    assert "SelfNest._lock->SelfNest._lock" in keys


# ---------------------------------------------------------------------------
# resource-pairing
# ---------------------------------------------------------------------------

def test_pair_span_detected_and_clean_twin(tmp_path):
    bad = """
    from paddle_tpu.telemetry import span_begin, span_end

    def discarded():
        span_begin("serving/x")

    def leaked():
        s = span_begin("serving/y")
        return None
    """
    r = run_on(tmp_path, bad, ["resource-pairing"])
    assert rules_of(r) == ["pair-span"]
    assert len(r.violations) == 2

    good = """
    from paddle_tpu.telemetry import span_begin, span_end

    def paired():
        s = span_begin("serving/x")
        try:
            return 1
        finally:
            span_end(s)

    def handed_off(sink):
        s = span_begin("serving/y")
        sink.adopt(s)     # ownership transfer

    def stored(self_like):
        self_like._span = span_begin("serving/z")  # escape via store
    """
    r = run_on(tmp_path, good, ["resource-pairing"])
    assert r.violations == []


def test_pair_acquire_detected_and_clean_twin(tmp_path):
    bad = """
    def missing(self):
        self._lock.acquire()
        return work()

    def unsafe(self):
        self._lock.acquire()
        work()                  # raises -> lock held forever
        self._lock.release()
    """
    r = run_on(tmp_path, bad, ["resource-pairing"])
    assert rules_of(r) == ["pair-acquire"]
    msgs = " ".join(v.message for v in r.violations)
    assert "no matching" in msgs and "exception path" in msgs

    good = """
    def with_stmt(self):
        with self._lock:
            return work()

    def try_finally(self):
        self._lock.acquire()
        try:
            return work()
        finally:
            self._lock.release()

    def timeout_probe(self):
        if not self._lock.acquire(timeout=0.05):
            return None
        try:
            return work()
        finally:
            self._lock.release()
    """
    r = run_on(tmp_path, good, ["resource-pairing"])
    assert r.violations == []


def test_pair_refcount_detected_and_clean_twin(tmp_path):
    bad = """
    class Leaky:
        def grab(self):
            self._pool.alloc()          # discarded page

        def hold(self, pages):
            self._pool.incref(pages)    # never decref'd, no transfer
    """
    r = run_on(tmp_path, bad, ["resource-pairing"])
    assert rules_of(r) == ["pair-refcount"]
    # discarded alloc + local incref + class-level imbalance
    assert len(r.violations) == 3

    good = """
    class Balanced:
        def grab(self, slot):
            p = self._pool.alloc()
            if p is None:
                return False
            slot.pages.append(p)        # ownership transfer
            return True

        def adopt(self, slot, pages):
            self._pool.incref(pages)
            slot.pages = list(pages)    # ownership transfer

        def release(self, slot):
            self._pool.decref(slot.pages)
            slot.pages = []
    """
    r = run_on(tmp_path, good, ["resource-pairing"])
    assert r.violations == []


def test_pair_draft_detected_and_clean_twin(tmp_path):
    bad = """
    class Speculator:
        def round(self, slot):
            keep = self._acquire_draft_pages(slot, 4)
            return keep                 # no rollback/release path
    """
    r = run_on(tmp_path, bad, ["resource-pairing"])
    assert rules_of(r) == ["pair-draft"]
    assert r.violations[0].key.endswith(":draft-pages")

    good = """
    class Speculator:
        def round(self, slot):
            keep = self._acquire_draft_pages(slot, 4)
            self._rollback_draft_pages(slot, keep)

        def fail_path(self, slot):
            self._acquire_draft_pages(slot, 4)
            self._release_pages(slot)   # whole-slot release also pairs

        def _acquire_draft_pages(self, slot, n):
            # the helper itself is exempt: it rolls back internally
            # on the exhaustion path before re-raising
            return len(slot.pages)
    """
    r = run_on(tmp_path, good, ["resource-pairing"])
    assert r.violations == []


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

def test_donation_use_after_alias_detected_and_clean_twin(tmp_path):
    bad = """
    from paddle_tpu import layers

    def block(cache_k, k, positions, table, lens):
        layers.kv_pool_write(cache_k, k, positions, table, lens)
        return layers.matmul(cache_k, k)   # reads the donated buffer
    """
    r = run_on(tmp_path, bad, ["donation-safety"])
    assert rules_of(r) == ["donation-use-after-alias"]
    assert r.violations[0].key.endswith(":cache_k")

    good = """
    from paddle_tpu import layers

    def block(cache_k, k, positions, table, lens):
        cache_k = layers.kv_pool_write(cache_k, k, positions, table, lens)
        return layers.matmul(cache_k, k)   # rebound: the op's output

    def last_use(cache_k, k, positions, table, lens):
        out = layers.kv_pool_write(cache_k, k, positions, table, lens)
        return out                          # donated name never read

    def tuple_rebind(cache_k, cache_v, k, v, pos, bt, ln):
        cache_k, cache_v = (layers.kv_pool_write(cache_k, k, pos, bt, ln),
                            layers.kv_pool_write(cache_v, v, pos, bt, ln))
        return layers.matmul(cache_k, cache_v)
    """
    r = run_on(tmp_path, good, ["donation-safety"])
    assert r.violations == []


def test_donation_jit_callable_detected_and_clean_twin(tmp_path):
    bad = """
    import jax

    class Engine:
        def build(self):
            self._adopt_scatter = jax.jit(
                lambda pool, idx, rows: pool.at[idx].set(rows),
                donate_argnums=(0,))

        def adopt(self, pool, idx, rows):
            self._adopt_scatter(pool, idx, rows)
            return pool.sum()           # reads the donated buffer
    """
    r = run_on(tmp_path, bad, ["donation-safety"])
    assert rules_of(r) == ["donation-use-after-alias"]
    assert r.violations[0].key.endswith(":pool")

    good = """
    import jax

    class Engine:
        def build(self, donate_state):
            self._adopt_scatter = jax.jit(
                lambda pool, idx, rows: pool.at[idx].set(rows),
                donate_argnums=(0,) if donate_state else ())

        def adopt(self, pool, idx, rows):
            pool = self._adopt_scatter(pool, idx, rows)
            return pool.sum()           # rebound same statement

        def multiline(self, pool, idx,
                      rows):
            out = self._adopt_scatter(pool,
                                      idx, rows)
            return out                  # donated name never read after

        def plain(self, pool):
            self._undonated(pool)
            return pool.sum()           # not a donating callable
    """
    r = run_on(tmp_path, good, ["donation-safety"])
    assert r.violations == []


# ---------------------------------------------------------------------------
# flag-hygiene
# ---------------------------------------------------------------------------

def test_flag_hygiene_rules(tmp_path, monkeypatch):
    readme = tmp_path / "README.md"
    readme.write_text("docs: `FLAGS_fx_documented` is a knob\n")
    monkeypatch.setattr(flag_hygiene, "README_PATH", str(readme))
    monkeypatch.setattr(flag_hygiene, "READ_EVIDENCE_ROOTS", ())
    bad = """
    from paddle_tpu.flags import register_flag, flag_value

    register_flag("FLAGS_fx_dead", 0, "never read")
    register_flag("FLAGS_fx_documented", 0, "read below")

    def f():
        flag_value("FLAGS_fx_documented")
        return flag_value("FLAGS_fx_typod")     # never registered
    """
    r = run_on(tmp_path, bad, ["flag-hygiene"])
    got = {(v.rule, v.key) for v in r.violations}
    assert ("flag-undefined", "FLAGS_fx_typod") in got
    assert ("flag-unused", "FLAGS_fx_dead") in got
    assert ("flag-undocumented", "FLAGS_fx_dead") in got
    # defined + read + documented -> clean
    assert not any(k == "FLAGS_fx_documented" for _, k in got)

    good = """
    from paddle_tpu.flags import register_flag, flag_value

    register_flag("FLAGS_fx_documented", 0, "read below")

    def f():
        return flag_value("FLAGS_fx_documented")
    """
    r = run_on(tmp_path, good, ["flag-hygiene"])
    assert r.violations == []


# ---------------------------------------------------------------------------
# exception-policy + stat-catalog (absorbed tools)
# ---------------------------------------------------------------------------

def test_bare_except_pass_detected_and_waiver_honored(tmp_path):
    bad = """
    def f():
        try:
            x = 1
        except Exception:
            pass
    """
    r = run_on(tmp_path, bad, ["exception-policy"])
    assert rules_of(r) == ["bare-except-pass"]

    good = """
    def f():
        try:
            x = 1
        except StopIteration:
            pass  # ok: generator drained
        try:
            y = 2
        except Exception:
            log("boom")
            pass
    """
    r = run_on(tmp_path, good, ["exception-policy"])
    assert r.violations == []


def test_stat_undocumented_detected_and_clean_twin(tmp_path, monkeypatch):
    readme = tmp_path / "README.md"
    readme.write_text("**Stat catalog** `fx_known_stat`\n")
    monkeypatch.setattr(stat_catalog, "README_PATH", str(readme))
    bad = """
    from paddle_tpu.monitor import stat_add
    from paddle_tpu import telemetry

    def f():
        stat_add("fx_known_stat")
        stat_add("fx_unknown_stat")
        telemetry.gauge_set("fx_unknown_gauge", 1.0)
        stat_add(f"dynamic_{f.__name__}")   # non-literal: out of scope
    """
    r = run_on(tmp_path, bad, ["stat-catalog"])
    assert {v.key for v in r.violations} == \
        {"fx_unknown_stat", "fx_unknown_gauge"}

    good = bad.replace('"fx_unknown_stat"', '"fx_known_stat"').replace(
        '"fx_unknown_gauge"', '"fx_known_stat"')
    r = run_on(tmp_path, good, ["stat-catalog"])
    assert r.violations == []


# ---------------------------------------------------------------------------
# waivers / baseline machinery
# ---------------------------------------------------------------------------

def test_inline_gc_ok_waiver_suppresses(tmp_path):
    src = LOCK_BARE.replace(
        '"draining": self._draining}',
        '"draining": self._draining}  # gc-ok: lock-bare-access '
        'point-in-time probe')
    r = run_on(tmp_path, src, ["lock-discipline"])
    assert not any(v.key.endswith("_draining") for v in r.violations)
    assert any(v.key.endswith("_draining") and "inline" in reason
               for v, reason in r.waived)


def test_baseline_waives_and_goes_stale(tmp_path):
    mod = tmp_path / "fixture.py"
    mod.write_text(textwrap.dedent(LOCK_ORDER))
    rel = os.path.relpath(str(mod), REPO).replace(os.sep, "/")
    bl = tmp_path / "bl.txt"
    bl.write_text(
        f"lock-order  {rel}  TwoLocks._a->TwoLocks._b  -- fixture\n"
        f"lock-order  {rel}  TwoLocks._b->TwoLocks._a  -- fixture\n"
        f"lock-order  {rel}  TwoLocks.nothing->x  -- stale entry\n"
        f"lock-order {rel} missing-reason\n")
    r = core.run(roots=[str(mod)], rule_filter=["lock-discipline"],
                 baseline_path=str(bl))
    assert len(r.waived) == 2
    got = rules_of(r)
    assert "stale-waiver" in got and "baseline-format" in got
    assert "lock-order" not in got


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="unknown rule"):
        core.run(rule_filter=["no-such-rule"], roots=["tools"])


# ---------------------------------------------------------------------------
# the real tree is clean (the acceptance bar: fixes landed, waivers
# carry reasons) and the CLI contract holds
# ---------------------------------------------------------------------------

def test_real_tree_scans_clean():
    r = core.run()
    assert r.violations == [], "\n".join(
        v.render() for v in r.violations)
    # every waiver that applies carries a reason string
    assert all(reason for _, reason in r.waived)


def test_subset_roots_scan_clean():
    """A subset-root run must not manufacture violations: flag reads
    still resolve against the registry file even when it is outside
    the roots, and baseline waivers for out-of-scope files are not
    reported stale."""
    for roots in (["paddle_tpu/serving"], ["tools"]):
        r = core.run(roots=roots)
        assert r.violations == [], (roots, "\n".join(
            v.render() for v in r.violations))


def test_missing_root_is_an_error():
    with pytest.raises(FileNotFoundError, match="root not found"):
        core.run(roots=["no_such_directory_anywhere"])


def test_cli_json_stable_and_sorted(tmp_path):
    out1 = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    out2 = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out1.returncode == 0, out1.stdout + out1.stderr
    assert out1.stdout == out2.stdout  # byte-stable across runs
    payload = json.loads(out1.stdout)
    assert payload["ok"] is True
    assert payload["passes"] == sorted(payload["passes"])
    waived = payload["waived"]
    assert waived == sorted(
        waived, key=lambda v: (v["path"], v["line"], v["rule"],
                               v["key"], v["message"]))


def test_cli_rule_filter_and_exit_code(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    x = 1\nexcept Exception:\n    pass\n")
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--rule",
         "exception-policy", "--baseline", "", str(bad)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 1
    assert "bare-except-pass" in r.stdout


# ---------------------------------------------------------------------------
# runtime lock-order sanitizer
# ---------------------------------------------------------------------------

def test_locksan_detects_ab_ba_inversion():
    from paddle_tpu import locksan

    locksan.clear_violations()
    locksan.enable(raise_on_violation=True)
    try:
        A = threading.Lock()
        B = threading.Lock()
        boom = []

        def t_forward():
            with A:
                with B:
                    pass

        def t_backward():
            try:
                with B:
                    with A:       # closes the cycle
                        pass
            except locksan.LockOrderError as e:
                boom.append(str(e))

        for fn in (t_forward, t_backward):
            th = threading.Thread(target=fn)
            th.start()
            th.join(10)
        assert len(boom) == 1 and "inversion" in boom[0]
        assert len(locksan.violations()) == 1
        # the failed acquire gave the real lock back: A is free
        assert A.acquire(timeout=1)
        A.release()
    finally:
        locksan.disable()
        locksan.clear_violations()


def test_locksan_record_mode_reports_each_inversion_once():
    """FLAGS_debug_lock_order mode (record, no raise): a hot-path
    inversion hit N times yields ONE violation, not unbounded
    growth in a long-running replica."""
    from paddle_tpu import locksan

    locksan.clear_violations()
    locksan.enable(raise_on_violation=False)
    try:
        A = threading.Lock()
        B = threading.Lock()

        def forward():
            with A:
                with B:
                    pass

        def backward():
            with B:
                with A:
                    pass

        for fn in (forward, backward, backward, backward):
            th = threading.Thread(target=fn)
            th.start()
            th.join(10)
        assert len(locksan.violations()) == 1, locksan.violations()
    finally:
        locksan.disable()
        locksan.clear_violations()


def test_locksan_cross_thread_lock_handoff_is_legal():
    """A plain Lock acquired in one thread and released in another
    (the handoff/token pattern) is legal Python: no violation, and
    the acquirer's held-stack entry is unwound so later nesting in
    that thread records no stale edges."""
    from paddle_tpu import locksan

    locksan.clear_violations()
    locksan.enable(raise_on_violation=True)
    try:
        token = threading.Lock()
        A = threading.Lock()
        token.acquire()          # main thread holds the token

        th = threading.Thread(target=token.release)  # handoff release
        th.start()
        th.join(10)
        # if the stale entry survived, this nesting would record a
        # bogus token->A edge from the main thread
        with A:
            pass
        assert locksan.violations() == [], locksan.violations()
    finally:
        locksan.disable()
        locksan.clear_violations()


def test_locksan_clean_patterns_record_nothing():
    from paddle_tpu import locksan

    locksan.clear_violations()
    locksan.enable(raise_on_violation=True)
    try:
        A = threading.Lock()
        R = threading.RLock()
        cv = threading.Condition()

        with A:
            with R:
                with R:           # reentrant: legal
                    pass
        done = []

        def waiter():
            with cv:
                while not done:
                    cv.wait(1.0)

        th = threading.Thread(target=waiter)
        th.start()
        with cv:                  # Condition round-trip through the
            done.append(1)        # wrapped RLock (wait/notify)
            cv.notify_all()
        th.join(10)
        assert locksan.violations() == []
    finally:
        locksan.disable()
        locksan.clear_violations()
