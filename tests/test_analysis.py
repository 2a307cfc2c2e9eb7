"""shardlint analyzer tests — jax-free, so they run first and fast.

Per rule: one fixture-proven true positive and one near-miss negative
(the shape that LOOKS like the bug but is safe), plus the clean-tree
gate (``lint`` exits 0 on this repo with the committed empty baseline)
and the PR-12 regression: deleting the ``attn`` static from a real
``record_shape_key`` call makes the dispatch-statics rule fail, naming
the site.
"""

import json
import os
import shutil
import threading

import pytest

from llm_sharding_tpu.analysis import (
    core,
    lockorder,
    rule_dispatch,
    rule_donation,
    rule_lockorder,
    rule_metrics,
    rule_trace,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "llm_sharding_tpu")


def make_pkg(tmp_path, files, readme=""):
    """Build a throwaway package tree for rule fixtures: ``files`` maps
    package-relative paths to source; README.md lands at the repo root."""
    root = tmp_path / "fakepkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    (tmp_path / "README.md").write_text(readme)
    return core.Package(str(root))


JIT_PRELUDE = '''
import functools
import jax

@functools.partial(
    jax.jit, static_argnames=("tp", "attn"), donate_argnums=()
)
def serve_thing(cfg, state, tp=1, attn="xla"):
    return state

@functools.partial(jax.jit, donate_argnums=(1,))
def donate_prog(cfg, state):
    return state
'''


# --------------------------------------------------------- dispatch-statics

def test_dispatch_statics_true_positive(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": JIT_PRELUDE + '''
def drive(srv, attn):
    record_shape_key("serve_thing", (srv.tp,))
    return serve_thing(None, srv.state, tp=srv.tp, attn=attn)
'''})
    fs = rule_dispatch.check(pkg)
    assert len(fs) == 1
    assert "attn" in fs[0].message and "serve_thing" in fs[0].message


def test_dispatch_statics_near_miss_key_covers_static(tmp_path):
    # identical dispatch, but the key names the static — and a constant
    # static needs no key entry at all
    pkg = make_pkg(tmp_path, {"mod.py": JIT_PRELUDE + '''
def drive(srv, attn):
    record_shape_key("serve_thing", (srv.tp, attn))
    return serve_thing(None, srv.state, tp=srv.tp, attn=attn)

def drive_const(srv):
    record_shape_key("serve_thing", (srv.tp,))
    return serve_thing(None, srv.state, tp=srv.tp, attn="xla")
'''})
    assert rule_dispatch.check(pkg) == []


def test_dispatch_statics_pr12_regression(tmp_path):
    """The PR-12 bug, reverted locally: drop `attn` from a real serve_chunk
    shape key in runtime/server.py — lint must fail naming the site."""
    root = tmp_path / "llm_sharding_tpu"
    for rel in ("runtime/server.py", "parallel/serve.py"):
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(PKG, rel), dst)
    src = (root / "runtime/server.py").read_text()
    mutated = src.replace(
        "self.kv_block_size, attn, self.kv_dtype)",
        "self.kv_block_size, self.kv_dtype)", 1,
    )
    assert mutated != src, "serve_chunk shape key moved — update the test"
    (root / "runtime/server.py").write_text(mutated)
    shutil.copy(os.path.join(REPO, "README.md"), tmp_path / "README.md")
    fs = rule_dispatch.check(core.Package(str(root)))
    assert any(
        f.rule == "dispatch-statics" and "serve_chunk" in f.message
        and "'attn'" in f.message
        and f.path == "llm_sharding_tpu/runtime/server.py"
        for f in fs
    ), [f.message for f in fs]


# --------------------------------------------------------- donation-safety

def test_donation_read_after_dispatch_true_positive(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": JIT_PRELUDE + '''
def bad(srv):
    out = donate_prog(None, srv.state)
    return out, srv.state.k
'''})
    fs = rule_donation.check(pkg)
    assert len(fs) == 1
    assert "srv.state" in fs[0].message and "donated" in fs[0].message


def test_donation_near_miss_reassigned_same_statement(tmp_path):
    # the idiomatic safe shape: the dispatch statement rebinds the donated
    # path (or a prefix of it), so later reads see the fresh buffer
    pkg = make_pkg(tmp_path, {"mod.py": JIT_PRELUDE + '''
def good(srv):
    srv.state = donate_prog(None, srv.state)
    return srv.state.k

def good_branch(srv, fast):
    if fast:
        out = donate_prog(None, srv.state)
        return out
    return srv.state.k
'''})
    assert rule_donation.check(pkg) == []


def test_donation_retry_real_ok(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": JIT_PRELUDE + '''
def dispatch(self):
    def do_it():
        return donate_prog(None, self.state)
    self.state = self._retry("site_a", do_it)

def dispatch_safe(self):
    def do_it():
        return donate_prog(None, self.state)
    self.state = self._retry("site_b", do_it, real_ok=False)

def dispatch_nondonating(self):
    def do_read():
        return self.state
    return self._retry("site_c", do_read)
'''})
    fs = rule_donation.check(pkg)
    assert len(fs) == 1
    assert "site_a" in fs[0].message and "real_ok=False" in fs[0].message


# -------------------------------------------------------------- lock-order

LOCK_PRELUDE = '''
from llm_sharding_tpu.analysis.lockorder import named_lock
'''


def test_lockorder_rank_violation_true_positive(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": LOCK_PRELUDE + '''
class Bad:
    def __init__(self):
        self._lock = named_lock("obs.metrics.family")
        self._mutex = named_lock("server.mutex")

    def run(self):
        with self._lock:
            with self._mutex:
                pass
'''})
    fs = rule_lockorder.check(pkg, scope=("fakepkg/mod.py",))
    assert any(
        "holding 'obs.metrics.family'" in f.message
        and "'server.mutex'" in f.message for f in fs
    ), [f.message for f in fs]


def test_lockorder_near_miss_correct_nesting(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": LOCK_PRELUDE + '''
class Good:
    def __init__(self):
        self._lock = named_lock("obs.metrics.family")
        self._mutex = named_lock("server.mutex")

    def run(self):
        with self._mutex:
            with self._lock:
                pass
'''})
    assert rule_lockorder.check(pkg, scope=("fakepkg/mod.py",)) == []


def test_lockorder_raw_threading_lock_flagged(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": '''
import threading

class Sneaky:
    def __init__(self):
        self._lock = threading.Lock()
'''})
    fs = rule_lockorder.check(pkg, scope=("fakepkg/mod.py",))
    assert any("named_lock" in f.message for f in fs)


def test_lockorder_cross_method_edge(tmp_path):
    # the PR-4/7 class: holding _mutex while calling into a foreign
    # lock-holder whose lock ranks EARLIER — caught through the call graph
    pkg = make_pkg(tmp_path, {"mod.py": LOCK_PRELUDE + '''
class Router:
    def __init__(self):
        self._lock = named_lock("replica.router")

    def route(self):
        with self._lock:
            pass

class Server:
    def __init__(self):
        self._mutex = named_lock("server.mutex")
        self.router = Router()

    def step(self):
        with self._mutex:
            self.router.route()
'''})
    fs = rule_lockorder.check(pkg, scope=("fakepkg/mod.py",))
    assert any(
        "holding 'server.mutex'" in f.message
        and "'replica.router'" in f.message for f in fs
    ), [f.message for f in fs]


# ------------------------------------------------------- metrics-discipline

METRICS_README = """
| metric | type | meaning |
|---|---|---|
| `server_good_total{tenant,outcome}` | counter | documented + registered |
| `server_ghost_total` | counter | documented but never registered |
"""


def test_metrics_discipline_findings(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": '''
from .obs import REGISTRY

GOOD = REGISTRY.counter(
    "server_good_total", "fine", labels=("tenant", "outcome"),
)
NO_HELP = REGISTRY.counter("server_nohelp_total")

def feed(t):
    GOOD.labels(tenant=t, outcome="ok").inc()
    GOOD.labels(tenant=t, reason="oops").inc()
'''}, readme=METRICS_README)
    fs = rule_metrics.check(pkg)
    msgs = "\n".join(f.message for f in fs)
    assert "server_nohelp_total" in msgs and "help" in msgs
    assert "server_ghost_total" in msgs and "no registration" in msgs
    assert "inconsistent" in msgs  # the reason= feed site
    # the correct feed site is NOT flagged
    assert sum("inconsistent" in f.message for f in fs) == 1
    # undocumented: the helpless counter also has no README row
    assert any(
        "server_nohelp_total" in f.message and "no row" in f.message
        for f in fs
    )


def test_metrics_discipline_near_miss_clean(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": '''
from .obs import REGISTRY

GOOD = REGISTRY.counter(
    "server_good_total", "fine", labels=("tenant", "outcome"),
)

def feed(t):
    GOOD.labels(tenant=t, outcome="ok").inc()
'''}, readme="""
| metric | type | meaning |
|---|---|---|
| `server_good_total{tenant,outcome}` | counter | documented |
""")
    assert rule_metrics.check(pkg) == []


# --------------------------------------------------------- trace-discipline

TRACE_README = """
| span | emitted by | fields |
|---|---|---|
| `request` | server | fine |
| `phantom` | nobody | stale row |
"""


def test_trace_discipline_findings(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": '''
def finish(writer, trace):
    emit_span(writer, "request", trace=trace)
    emit_span(writer, "mystery", trace=trace)
'''}, readme=TRACE_README)
    fs = rule_trace.check(pkg)
    msgs = "\n".join(f.message for f in fs)
    assert "mystery" in msgs and "missing from" in msgs
    assert "phantom" in msgs and "nothing emits" in msgs
    assert not any("'request'" in f.message for f in fs)


def test_trace_discipline_near_miss_clean(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": '''
def finish(self, writer, trace):
    emit_span(writer, "request", trace=trace)
    self._span("phantom", x=1)
'''}, readme=TRACE_README)
    assert rule_trace.check(pkg) == []


def test_trace_discipline_holds_the_setup_ledgers_names_both_ways(tmp_path):
    """The set-up ledger's spans reach ``emit_span`` through a pass-through:
    their names are the package's ``setup.<word>`` constants (ISSUE 41)."""
    readme = TRACE_README + "| `setup.engine` | engine | whole |\n" \
        "| `setup.server.arena` | nobody | stale row |\n"
    pkg = make_pkg(tmp_path, {"mod.py": '''
ARENA = "setup.server.host"
def build(self, writer, trace):
    emit_span(writer, "request", trace=trace)
    self._span("phantom", x=1)
    with SETUP.span("setup.engine"):
        """a docstring that names setup.engine.put opens nothing"""
'''}, readme=readme)
    fs = rule_trace.check(pkg)
    assert sorted(f.key for f in fs) == [
        "stale:setup.server.arena", "undocumented:setup.server.host"]


# ------------------------------------------------------- gate + baseline

def test_clean_tree_lint_exit_zero():
    """THE gate: the repo's own lint is clean with the committed (empty)
    baseline. Any new finding fails this test before CI even gets to it."""
    rc = core.run_lint()
    assert rc == 0


def test_committed_baseline_is_empty():
    with open(core.default_baseline_path()) as f:
        assert json.load(f)["findings"] == []


def test_baseline_suppresses_known_findings(tmp_path):
    files = {"mod.py": JIT_PRELUDE + '''
def drive(srv, attn):
    record_shape_key("serve_thing", (srv.tp,))
    return serve_thing(None, srv.state, tp=srv.tp, attn=attn)
'''}
    pkg_root = tmp_path / "fakepkg"
    make_pkg(tmp_path, files)
    bl = tmp_path / "baseline.json"
    rc = core.run_lint(root=str(pkg_root), baseline_path=str(bl))
    assert rc == 1
    rc = core.run_lint(
        root=str(pkg_root), baseline_path=str(bl), write_baseline=True
    )
    assert rc == 0
    rc = core.run_lint(root=str(pkg_root), baseline_path=str(bl))
    assert rc == 0  # baselined, not fixed — but no NEW findings


def test_unknown_rule_is_usage_error():
    assert core.run_lint(only=["no-such-rule"]) == 2


def test_partial_rule_write_baseline_keeps_other_rules(tmp_path):
    """`lint --rule X --write-baseline` must not discard other rules'
    accepted fingerprints (fingerprints lead with '<rule>:')."""
    make_pkg(tmp_path, {"mod.py": JIT_PRELUDE + '''
def drive(srv, attn):
    record_shape_key("serve_thing", (srv.tp,))
    return serve_thing(None, srv.state, tp=srv.tp, attn=attn)
'''})
    pkg_root = str(tmp_path / "fakepkg")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(
        {"findings": ["lock-order:other.py:deadbeef0000"]}
    ))
    rc = core.run_lint(
        root=pkg_root, baseline_path=str(bl),
        only=["dispatch-statics"], write_baseline=True,
    )
    assert rc == 0
    fps = json.load(open(bl))["findings"]
    assert "lock-order:other.py:deadbeef0000" in fps
    assert any(fp.startswith("dispatch-statics:") for fp in fps)


def test_metrics_token_expansion_with_trailing_labels():
    """A README token combining mid-token {a,b} expansion AND a trailing
    label set keeps the expansion (only the label group strips)."""
    assert rule_metrics._expand_token(
        "server_requests_{submitted,completed}_total{tenant}"
    ) == ["server_requests_submitted_total",
          "server_requests_completed_total"]
    assert rule_metrics._expand_token(
        "server_arena_bytes{dtype=bf16|int8|fp8}"
    ) == ["server_arena_bytes"]


# ------------------------------------------------- runtime lock tracker

@pytest.fixture
def tracked():
    was = lockorder.enabled()
    lockorder.enable(True)
    yield
    lockorder.enable(was)


def test_tracker_violation_names_both_stacks(tracked):
    inner = lockorder.named_lock("obs.metrics.family")
    outer = lockorder.named_lock("server.mutex", "rlock")
    with outer:
        with inner:
            pass  # correct order
    with pytest.raises(lockorder.LockOrderViolation) as ei:
        with inner:
            with outer:
                pass
    msg = str(ei.value)
    assert "stack that acquired 'obs.metrics.family'" in msg
    assert "stack acquiring 'server.mutex'" in msg
    assert lockorder.held_names() == []  # fully released after the raise


def test_tracker_reentrant_and_equal_rank_ok(tracked):
    m1 = lockorder.named_lock("server.mutex", "rlock")
    m2 = lockorder.named_lock("server.mutex", "rlock")
    with m1:
        with m1:        # re-entrant same instance
            with m2:    # equal rank, other instance (dp migration shape)
                pass
    assert lockorder.held_names() == []


def test_tracker_condition_wrapper(tracked):
    cv = lockorder.named_lock("disagg.handoff", "condition")
    hits = []

    def waiter():
        with cv:
            cv.wait_for(lambda: hits, timeout=5.0)
            hits.append("woke")

    t = threading.Thread(target=waiter)
    t.start()
    with cv:
        hits.append("go")
        cv.notify_all()
    t.join(timeout=5.0)
    assert hits == ["go", "woke"]


def test_tracker_cross_thread_independence(tracked):
    # held sets are thread-local: another thread's outer lock does not
    # poison this thread's ordering
    inner = lockorder.named_lock("obs.metrics.family")
    outer = lockorder.named_lock("server.mutex", "rlock")
    errs = []

    def other():
        try:
            with outer:
                with inner:
                    pass
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    with inner:
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=5.0)
    assert errs == []


def test_named_lock_rejects_unregistered_names():
    with pytest.raises(ValueError):
        lockorder.named_lock("not.a.known.lock")
    with pytest.raises(ValueError):
        lockorder.named_lock("server.mutex", "spinlock")


def test_named_lock_plain_when_disabled():
    was = lockorder.enabled()
    lockorder.enable(False)
    try:
        lk = lockorder.named_lock("server.mutex", "rlock")
        assert not isinstance(lk, lockorder._TrackedBase)
        with lk:
            pass
    finally:
        lockorder.enable(was)


def test_order_has_no_duplicates():
    assert len(set(lockorder.ORDER)) == len(lockorder.ORDER)
