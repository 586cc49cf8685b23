"""dtxlint (datatunerx_tpu/analysis): one true-positive and one clean
fixture per rule, plus framework behavior — inline suppressions, baseline
load/partition, JSON output, config parsing, and the CI contract that the
repo itself lints clean.

The DTX006/DTX007 positive fixtures reproduce the PRE-FIX gateway
drain-leak shape from ROADMAP ("/admin/drain never reaps"): a replica set
that spawns subprocesses, drains on request, and never terminates what it
drained — exactly what PR 4 fixed in gateway/server.py.
"""

import ast
import dataclasses
import json
import subprocess
import textwrap
import time

import pytest

from datatunerx_tpu.analysis.baseline import (
    load_baseline,
    partition,
    save_baseline,
)
from datatunerx_tpu.analysis.cli import main as dtxlint_main
from datatunerx_tpu.analysis.config import LintConfig, load_config
from datatunerx_tpu.analysis.core import lint_paths, lint_source
from datatunerx_tpu.analysis.fix import (
    OverlapError,
    SpanEdit,
    apply_edits,
    fix_source,
)
from datatunerx_tpu.analysis.program import lint_program

CFG = LintConfig(mesh_axes=("dp", "fsdp", "tp", "sp"))


def run(src, config=CFG):
    res = lint_source(textwrap.dedent(src), path="fixture.py", config=config)
    return res


def rule_ids(src, config=CFG):
    return [f.rule for f in run(src, config).findings]


# ------------------------------------------------------------------ DTX001
def test_dtx001_flags_host_sync_reachable_from_hot_function():
    src = """
    import jax
    import numpy as np

    def log_metrics(m):
        return float(m["loss"])

    def train_step(state, batch):
        out = state.apply(batch)
        log_metrics(out)
        return np.asarray(out)
    """
    ids = rule_ids(src)
    assert ids.count("DTX001") == 2  # float() via call graph + np.asarray


def test_dtx001_clean_outside_hot_path_and_on_constants():
    src = """
    import numpy as np

    def train_step(state, batch):
        return state.apply(batch)

    def summarize(metrics):
        # same calls, but not reachable from a hot function
        return float(metrics["loss"]), np.asarray(metrics["hist"])

    def parse(v):
        return float("1.5")
    """
    assert rule_ids(src) == []


# ------------------------------------------------------------------ DTX002
def test_dtx002_flags_jit_in_loop_and_unstable_static_args():
    src = """
    import jax

    def compile_all(fns):
        out = []
        for f in fns:
            out.append(jax.jit(f))
        return out

    bad = jax.jit(lambda x: x, static_argnums={0, 1})
    """
    ids = rule_ids(src)
    assert ids.count("DTX002") == 2


def test_dtx002_clean_for_hoisted_jit_called_in_loop():
    src = """
    import jax

    step = jax.jit(lambda x: x + 1)

    def run(n):
        for i in range(n):
            step(i)
        return jax.jit(lambda y: y, static_argnums=(0,))
    """
    assert rule_ids(src) == []


# ------------------------------------------------------------------ DTX003
def test_dtx003_flags_python_branch_on_traced_value():
    src = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        if jnp.any(x > 0):
            return x
        return -x
    """
    assert rule_ids(src) == ["DTX003"]


def test_dtx003_allows_static_shape_branches_and_wrapped_names():
    src = """
    import jax
    import jax.numpy as jnp

    def impl(x):
        if x.ndim == 2:  # static under tracing
            return jnp.sum(x, axis=-1)
        return jnp.where(x > 0, x, -x)

    f = jax.jit(impl)

    def eager(x):
        # not jitted: Python control flow on values is fine
        if jnp.any(x > 0):
            return x
        return -x
    """
    assert rule_ids(src) == []


# ------------------------------------------------------------------ DTX004
def test_dtx004_flags_double_consumption_and_loop_reuse():
    src = """
    import jax

    def double(key):
        a = jax.random.normal(key, (2,))
        b = jax.random.uniform(key, (2,))
        return a + b

    def loop(key):
        return [jax.random.normal(key, (2,)) for _ in range(3)] if False \\
            else _loop(key)

    def _loop(key):
        out = []
        for i in range(3):
            out.append(jax.random.normal(key, (2,)))
        return out
    """
    ids = rule_ids(src)
    assert ids.count("DTX004") == 2


def test_dtx004_clean_split_branches_loop_carry_and_fold_in():
    src = """
    import jax

    def good(key, flag):
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (2,))
        if flag:
            b = jax.random.uniform(k2, (2,))
        else:
            b = jax.random.normal(k2, (2,))
        return a + b

    def carry(key):
        out = []
        for i in range(3):
            key, sub = jax.random.split(key)
            out.append(jax.random.normal(sub, (2,)))
        return out

    def streams(key):
        # fold_in with distinct data is the documented idiom, not reuse
        return [jax.random.normal(jax.random.fold_in(key, i), (2,))
                for i in range(3)]
    """
    assert rule_ids(src) == []


# ------------------------------------------------------------------ DTX005
def test_dtx005_flags_undeclared_axis_name():
    src = """
    from jax.sharding import PartitionSpec as P

    def spec():
        return P("data", None)
    """
    assert rule_ids(src) == ["DTX005"]


def test_dtx005_clean_declared_axes_and_quiet_without_axes():
    src = """
    from jax.sharding import PartitionSpec as P

    def spec():
        return P(("dp", "fsdp"), None, "tp")
    """
    assert rule_ids(src) == []
    # no declared axes configured → nothing to check against
    assert rule_ids('from jax.sharding import PartitionSpec as P\n'
                    'x = P("whatever")\n', config=LintConfig()) == []


def test_dtx005_flags_collective_axis_name_drift():
    # positional axis_name
    src = """
    import jax

    def all_reduce(x):
        return jax.lax.psum(x, "data")
    """
    assert rule_ids(src) == ["DTX005"]
    # keyword + tuple form, and axis_index's position-0 argument
    src2 = """
    import jax

    def gather(x):
        i = jax.lax.axis_index("mdl")
        return jax.lax.all_gather(x, axis_name=("dp", "model")), i
    """
    assert rule_ids(src2) == ["DTX005", "DTX005"]


def test_dtx005_clean_collectives_declared_or_variable_axis():
    src = """
    import jax

    def reduce_ok(x, axis_name):
        y = jax.lax.pmean(x, "dp")
        z = jax.lax.psum(x, ("dp", "fsdp"))
        i = jax.lax.axis_index("tp")
        # a VARIABLE axis name (ring attention's parameter) is out of
        # static reach — must not be flagged
        return jax.lax.ppermute(y + z + i, axis_name, [(0, 1)])
    """
    assert rule_ids(src) == []


# ------------------------------------------------------------------ DTX006
# the pre-fix /admin/drain shape: a public method flips state the
# supervisor thread reconciles on, with no lock
DRAIN_LEAK_CLASS = """
import subprocess
import threading


class ReplicaSet:
    def __init__(self):
        self._lock = threading.Lock()
        self.target = 0
        self._procs = {}
        self._t = threading.Thread(target=self._supervise, daemon=True)
        self._t.start()

    def _supervise(self):
        while True:
            if len(self._procs) < self.target:
                self.spawn(str(len(self._procs)))

    def spawn(self, name):
        self._procs[name] = subprocess.Popen(["serve"])

    def scale(self, n):
        self.target = n

    def drain(self, name):
        self._procs[name].draining = True
"""


def test_dtx006_flags_pre_fix_drain_leak_shape_unlocked_public_write():
    ids = rule_ids(DRAIN_LEAK_CLASS)
    assert "DTX006" in ids  # scale() writes self.target, thread reads it


def test_dtx006_clean_when_writes_hold_the_lock():
    src = """
    import threading

    class ReplicaSet:
        def __init__(self):
            self._lock = threading.Lock()
            self.target = 0
            self._t = threading.Thread(target=self._loop, daemon=True)

        def _loop(self):
            while True:
                with self._lock:
                    n = self.target

        def scale(self, n):
            with self._lock:
                self.target = n
    """
    assert rule_ids(src) == []


# ------------------------------------------------------------------ DTX007
def test_dtx007_flags_pre_fix_drain_leak_shape_unreaped_subprocess():
    ids = rule_ids(DRAIN_LEAK_CLASS)
    # spawn() stores a Popen in self._procs and NO method of the class
    # ever terminates/joins values from it — the zombie-per-drain leak
    assert "DTX007" in ids


def test_dtx007_clean_when_a_method_reaps_and_for_escaping_handles():
    src = """
    import subprocess
    import threading

    class ReplicaSet:
        def __init__(self):
            self._procs = {}

        def spawn(self, name):
            self._procs[name] = subprocess.Popen(["serve"])

        def close(self):
            procs = list(self._procs.values())
            for proc in procs:
                proc.terminate()

    def run_once():
        proc = subprocess.Popen(["true"])
        proc.wait()

    def fire_and_forget(fn):
        threading.Thread(target=fn, daemon=True).start()

    def handoff():
        return subprocess.Popen(["true"])
    """
    assert rule_ids(src) == []


# ------------------------------------------------------------------ DTX008
def test_dtx008_flags_module_level_and_default_arg_device_work():
    src = """
    import jax
    import jax.numpy as jnp

    TABLE = jnp.ones((8,))

    def f(x, fill=jnp.zeros((4,))):
        return x + fill

    N_DEV = jax.device_count()
    """
    assert rule_ids(src) == ["DTX008"] * 3


def test_dtx008_clean_for_lazy_work_jit_wrappers_and_dtypes():
    src = """
    import jax
    import jax.numpy as jnp

    DTYPE = jnp.float32

    def make_table():
        return jnp.ones((8,))

    f = jax.jit(make_table)
    g = lambda: jnp.zeros((4,))
    """
    assert rule_ids(src) == []


# ------------------------------------------------------------------ DTX009
def test_dtx009_flags_blocking_calls_under_lock():
    src = """
    import queue
    import subprocess
    import threading

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()

        def tick(self):
            with self._lock:
                item = self._q.get()
                subprocess.run(["sync-replica"])
            return item
    """
    ids = rule_ids(src)
    assert ids.count("DTX009") == 2  # unbounded .get() + subprocess.run


def test_dtx009_clean_bounded_waits_and_non_lock_contexts():
    src = """
    import threading

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self._session = Session()

        def tick(self, proc, item_q):
            with self._lock:
                item = item_q.get(timeout=1.0)
                proc.wait(timeout=10)
            with self._session:  # not a lock: naming-based on purpose
                proc.communicate()
            proc.wait()  # blocking, but no lock held
            return item
    """
    assert rule_ids(src) == []


# ------------------------------------------------------------------ DTX010
def test_dtx010_flags_read_after_donation():
    src = """
    import jax

    step = jax.jit(lambda s, b: s, donate_argnums=(0,))

    def run(state, batch):
        out = step(state, batch)
        return out, state
    """
    assert rule_ids(src) == ["DTX010"]


def test_dtx010_clean_loop_carry_and_rebind_before_read():
    src = """
    import jax

    step = jax.jit(lambda s, b: s, donate_argnums=(0,))

    def train(state, batches):
        for b in batches:
            state = step(state, b)
        return state

    def reset(state, batch):
        _ = step(state, batch)
        state = make_state()
        return state
    """
    assert rule_ids(src) == []


def test_dtx010_conditional_rebind_does_not_clear_fallthrough_read():
    # `if err: state = reset()` only rebinds on one path — the other still
    # reads the donated buffer and must flag; a read INSIDE the rebinding
    # branch (after its store) is clean
    src = """
    import jax

    step = jax.jit(lambda s, b: s, donate_argnums=(0,))

    def run(state, batch, err):
        out = step(state, batch)
        if err:
            state = make_state()
        return out, state

    def fine(state, batch, err):
        out = step(state, batch)
        if err:
            state = make_state()
            log(state)
        return out
    """
    assert rule_ids(src) == ["DTX010"]


def test_dtx010_flags_loop_backedge_without_rebind():
    # the decode-loop shape the rule exists for: state is donated every
    # iteration but never rebound, so iteration N+1 reads N's dead buffer;
    # a loop whose target (or body) rebinds the victim is clean
    src = """
    import jax

    step = jax.jit(lambda s, b: s, donate_argnums=(0,))

    def decode(state, batches):
        outs = []
        for b in batches:
            outs.append(step(state, b))
        return outs

    def fresh_each(states, batch):
        for state in states:
            _ = step(state, batch)
    """
    assert rule_ids(src) == ["DTX010"]


# ------------------------------------------------------------------ DTX011
def test_dtx011_flags_lexical_lock_order_inversion():
    src = """
    import threading

    class Pool:
        def __init__(self):
            self._alloc_lock = threading.Lock()
            self._stats_lock = threading.Lock()

        def allocate(self):
            with self._alloc_lock:
                with self._stats_lock:
                    return 1

        def report(self):
            with self._stats_lock:
                with self._alloc_lock:
                    return 2
    """
    ids = rule_ids(src)
    assert ids.count("DTX011") == 1
    f = [x for x in run(src).findings if x.rule == "DTX011"][0]
    assert "lock-order inversion" in f.message
    assert "opposite order" in f.message


def test_dtx011_clean_on_consistent_global_order():
    src = """
    import threading

    class Pool:
        def __init__(self):
            self._alloc_lock = threading.Lock()
            self._stats_lock = threading.Lock()

        def allocate(self):
            with self._alloc_lock:
                with self._stats_lock:
                    return 1

        def audit(self):
            with self._alloc_lock:
                with self._stats_lock:
                    return 2

        def stats_only(self):
            with self._stats_lock:
                return 3
    """
    assert rule_ids(src) == []


def test_dtx011_multi_item_with_uses_acquisition_order():
    # `with a, b` then `with b, a` is the same ABBA spelled compactly
    src = """
    import threading

    _a_lock = threading.Lock()
    _b_lock = threading.Lock()

    def fwd():
        with _a_lock, _b_lock:
            pass

    def rev():
        with _b_lock, _a_lock:
            pass
    """
    assert rule_ids(src).count("DTX011") == 1


# ------------------------------------------------------------------ DTX012
def test_dtx012_flags_daemon_thread_without_shutdown_evidence():
    src = """
    import threading

    class Ticker:
        def start(self):
            self._t = threading.Thread(target=self._run, daemon=True)
            self._t.start()

        def _run(self):
            while True:
                pass
    """
    ids = rule_ids(src)
    assert ids == ["DTX012"]
    f = run(src).findings[0]
    assert "no shutdown evidence" in f.message
    assert "self._t" in f.message


def test_dtx012_clean_with_stop_event_or_join():
    src = """
    import threading

    class EventLoop:
        def __init__(self):
            self._stop = threading.Event()

        def start(self):
            self._t = threading.Thread(target=self._run, daemon=True)
            self._t.start()

        def _run(self):
            while not self._stop.is_set():
                pass

        def close(self):
            self._stop.set()

    class Joined:
        def start(self):
            self._t = threading.Thread(target=print, daemon=True)
            self._t.start()

        def close(self):
            self._t.join(timeout=5)

    class Scoped:
        def run_once(self):
            t = threading.Thread(target=print, daemon=True)
            t.start()
            t.join()
    """
    assert rule_ids(src) == []


def test_dtx012_local_handle_escaping_to_attr_uses_class_evidence():
    # the AdapterRegistry/Gateway shape: a local handle appended to (or
    # aliased into) a self attribute that close() drains and joins
    src = """
    import threading

    class Registry:
        def __init__(self):
            self._loaders = []

        def kick(self):
            t = threading.Thread(target=print, daemon=True)
            self._loaders.append(t)
            t.start()

        def close(self):
            workers = [w for w in self._loaders if w.is_alive()]
            for w in workers:
                w.join(timeout=5)

    class Promoter:
        def start(self):
            t = threading.Thread(target=print, daemon=True)
            self._promo = t
            t.start()

        def close(self):
            t = self._promo
            t.join(timeout=5)
    """
    assert rule_ids(src) == []


def test_dtx012_timer_cancel_counts_and_unstarted_ignored():
    src = """
    import threading

    class Debounce:
        def arm(self):
            self._timer = threading.Timer(1.0, print)
            self._timer.daemon = True
            self._timer.start()

        def close(self):
            self._timer.cancel()

    class NeverStarted:
        def build(self):
            self._t = threading.Thread(target=print, daemon=True)
    """
    assert rule_ids(src) == []


def test_dtx012_non_daemon_is_dtx007_territory():
    # no daemon flag: DTX012 stays quiet (DTX007 owns non-daemon handles)
    src = """
    import threading

    class Plain:
        def start(self):
            self._t = threading.Thread(target=print)
            self._t.start()
    """
    assert "DTX012" not in rule_ids(src)


# ------------------------------------------------------- hot-region markers
def test_hot_region_markers_flag_sync_inside_region_only():
    src = """
    import numpy as np

    def load_config(path):
        return np.asarray([1.0])  # called outside the region: cold

    def fetch_metrics(m):
        return np.asarray(m)  # called FROM the region: hot by propagation

    def main(batches):
        cfg = load_config("x")
        # dtxlint: hot-begin
        out = [fetch_metrics(b) for b in batches]
        # dtxlint: hot-end
        return cfg, out
    """
    res = run(src)
    assert [f.rule for f in res.findings] == ["DTX001"]
    assert res.findings[0].line == 8  # the asarray inside fetch_metrics


def test_hot_region_sync_flagged_lexically_and_clean_without_markers():
    marked = """
    def main(batches):
        # dtxlint: hot-begin
        for b in batches:
            loss = float(step(b))
        # dtxlint: hot-end
        return loss
    """
    assert rule_ids(marked) == ["DTX001"]
    unmarked = "\n".join(ln for ln in textwrap.dedent(marked).splitlines()
                         if "dtxlint" not in ln)
    assert rule_ids(unmarked) == []


# ------------------------------------------------- program graph (tentpole)
def _write_pkg(tmp_path, files):
    """A real on-disk package so module_name_for_path resolves pkg.*
    imports; lint_program stitches the per-module graphs together."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    for name, src in files.items():
        (pkg / name).write_text(textwrap.dedent(src))
    return pkg


def _prog(pkg):
    res, stats = lint_program([str(pkg)], config=LintConfig(cache=""))
    return res


def test_program_graph_flags_cross_module_sync_from_hot_root(tmp_path):
    pkg = _write_pkg(tmp_path, {
        "helpers.py": """
            import numpy as np

            def to_host(x):
                return np.asarray(x)
        """,
        "train.py": """
            from pkg.helpers import to_host

            def train_step(state, batch):
                return to_host(state)
        """,
    })
    findings = _prog(pkg).findings
    assert [f.rule for f in findings] == ["DTX001"]
    assert "helpers.py" in findings[0].path  # flagged where the sync lives
    assert "train_step" in findings[0].message  # ... naming the hot root


def test_program_graph_clean_when_helper_not_reachable_from_hot(tmp_path):
    pkg = _write_pkg(tmp_path, {
        "helpers.py": """
            import numpy as np

            def to_host(x):
                return np.asarray(x)
        """,
        "train.py": """
            from pkg.helpers import to_host

            def train_step(state, batch):
                return state

            def summarize(metrics):
                return to_host(metrics)  # cold caller: no finding
        """,
    })
    assert _prog(pkg).findings == []


def test_program_graph_flags_blocking_leaf_across_modules(tmp_path):
    pkg = _write_pkg(tmp_path, {
        "net.py": """
            import requests

            def fetch(url):
                return requests.get(url)
        """,
        "pool.py": """
            import threading

            from pkg.net import fetch

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()

                def refresh(self):
                    with self._lock:
                        return fetch("http://replica/health")
        """,
    })
    findings = _prog(pkg).findings
    assert [f.rule for f in findings] == ["DTX009"]
    assert "pool.py" in findings[0].path  # flagged at the locked call site
    assert "requests.get" in findings[0].message  # ... naming the leaf


def test_program_graph_ignores_thread_target_reference_edges(tmp_path):
    # the ManagedReplicaSet shape: reconcile (under lock) starts a reaper
    # THREAD whose target sleeps/waits — that work runs on another frame,
    # so the held-lock reachability must not follow the target= reference
    pkg = _write_pkg(tmp_path, {
        "pool.py": """
            import threading
            import time

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._stop = threading.Event()

                def _reap(self, name):
                    time.sleep(0.1)

                def _start_reap(self, name):
                    # daemon=True keeps this DTX007-clean and the _stop
                    # event keeps it DTX012-clean; the rule under test
                    # here is DTX009's reachability, not handle leaks
                    threading.Thread(
                        target=self._reap, args=(name,), daemon=True
                    ).start()

                def reconcile(self):
                    with self._lock:
                        self._start_reap("r0")

                def close(self):
                    self._stop.set()
        """,
    })
    assert _prog(pkg).findings == []


def test_program_graph_flags_cross_module_lock_inversion(tmp_path):
    # neither module inverts on its own — the cycle only exists across the
    # call edges: alloc.reserve holds ALLOC and calls stats.record (takes
    # STATS), while stats.flush holds STATS and calls alloc.touch (takes
    # ALLOC). Per-module DTX011 is lexical-only; the program pass stitches
    # the held-lock reachability.
    pkg = _write_pkg(tmp_path, {
        "alloc.py": """
            import threading

            from pkg.stats import record

            ALLOC_LOCK = threading.Lock()

            def reserve():
                with ALLOC_LOCK:
                    record()

            def touch():
                with ALLOC_LOCK:
                    return 1
        """,
        "stats.py": """
            import threading

            STATS_LOCK = threading.Lock()

            def record():
                with STATS_LOCK:
                    return 2

            def flush():
                from pkg.alloc import touch

                with STATS_LOCK:
                    touch()
        """,
    })
    findings = [f for f in _prog(pkg).findings if f.rule == "DTX011"]
    assert len(findings) == 1
    assert "pkg.alloc.ALLOC_LOCK" in findings[0].message
    assert "pkg.stats.STATS_LOCK" in findings[0].message


def test_program_graph_cross_module_consistent_order_clean(tmp_path):
    pkg = _write_pkg(tmp_path, {
        "alloc.py": """
            import threading

            from pkg.stats import record

            ALLOC_LOCK = threading.Lock()

            def reserve():
                with ALLOC_LOCK:
                    record()

            def touch():
                with ALLOC_LOCK:
                    return 1
        """,
        "stats.py": """
            import threading

            STATS_LOCK = threading.Lock()

            def record():
                with STATS_LOCK:
                    return 2

            def flush():
                with STATS_LOCK:
                    return 3
        """,
    })
    assert [f for f in _prog(pkg).findings if f.rule == "DTX011"] == []


def test_program_graph_adjudicates_handle_dropped_by_callee(tmp_path):
    pkg = _write_pkg(tmp_path, {
        "util.py": """
            def log_proc(proc):
                print(proc.pid)

            def reap(proc):
                proc.wait()
        """,
        "runner.py": """
            import subprocess

            from pkg.util import log_proc, reap

            def leaky():
                proc = subprocess.Popen(["serve"])
                log_proc(proc)  # callee only drops it: still ours to reap

            def fine():
                proc = subprocess.Popen(["serve"])
                log_proc(proc)
                reap(proc)  # a callee disposes: ownership handed over
        """,
    })
    findings = _prog(pkg).findings
    assert [f.rule for f in findings] == ["DTX007"]
    assert "runner.py" in findings[0].path
    assert "`proc`" in findings[0].message


# ----------------------------------------------------------- autofix (--fix)
def test_fix_hoists_jit_and_defers_default_arg():
    src = textwrap.dedent("""
        import jax
        import jax.numpy as jnp


        def compile_steps(n):
            out = []
            for i in range(n):
                step = jax.jit(lambda x: x + 1)
                out.append(step(i))
            return out


        def pad(x, fill=jnp.zeros((4,))):
            return x + fill
    """)
    fixed, res = fix_source(src, "m.py")
    assert res.changed and res.applied == 2 and res.unfixable == 0
    assert lint_source(fixed, path="m.py", config=CFG).findings == []
    # the hoist keeps the binding ABOVE the loop, inside the function
    assert fixed.index("step = jax.jit") < fixed.index("for i in range(n):")
    assert "fill=None" in fixed and "fill = jnp.zeros((4,))" in fixed
    # idempotent: a second pass has nothing left to do
    again, res2 = fix_source(fixed, "m.py")
    assert again == fixed and not res2.changed and res2.applied == 0


def test_fix_refuses_loop_dependent_jit_and_module_constants():
    src = textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        TABLE = jnp.ones((8,))

        def compile_all(fns):
            out = []
            for f in fns:
                g = jax.jit(f)
                out.append(g)
            return out
    """)
    fixed, res = fix_source(src, "m.py")
    # hoisting g=jax.jit(f) would change behavior (f varies per iteration)
    # and a module-level constant has no call-site-compatible rewrite:
    # both are REPORTED unfixable, and the source is left byte-identical
    assert fixed == src and not res.changed
    assert res.applied == 0 and res.unfixable == 2


def test_fix_dtx004_inserts_key_split_for_double_consumption():
    src = textwrap.dedent("""
        import jax


        def sample(key):
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))
            return a + b
    """)
    fixed, res = fix_source(src, "m.py")
    assert res.changed and res.applied == 1 and res.unfixable == 0
    assert lint_source(fixed, path="m.py", config=CFG).findings == []
    # the split lands BEFORE the first consumption (splitting after it
    # would itself reuse the consumed key) and rebinds the carry
    assert "key, key_split1 = jax.random.split(key)" in fixed
    assert fixed.index("= jax.random.split") < fixed.index("jax.random.normal")
    assert "jax.random.normal(key_split1, (4,))" in fixed
    assert "jax.random.uniform(key, (4,))" in fixed  # consumes the new carry
    # idempotent: nothing left to fix
    again, res2 = fix_source(fixed, "m.py")
    assert again == fixed and not res2.changed and res2.applied == 0


def test_fix_dtx004_loop_reuse_splits_per_iteration():
    src = textwrap.dedent("""
        import jax


        def rollout(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(key, (2,)))
            return out
    """)
    fixed, res = fix_source(src, "m.py")
    assert res.changed and res.applied == 1
    assert lint_source(fixed, path="m.py", config=CFG).findings == []
    # the split sits INSIDE the loop so every iteration advances the carry
    assert fixed.index("for i in range(n):") \
        < fixed.index("key, key_split1 = jax.random.split(key)")
    assert "jax.random.normal(key_split1, (2,))" in fixed
    again, res2 = fix_source(fixed, "m.py")
    assert again == fixed and not res2.changed


def test_fix_dtx004_respects_aliases_and_refuses_bare_imports():
    # module alias: the inserted split reuses the call's own module path
    src = textwrap.dedent("""
        from jax import random as jr


        def sample(key):
            a = jr.normal(key, (4,))
            b = jr.uniform(key, (4,))
            return a + b
    """)
    fixed, res = fix_source(src, "m.py")
    assert res.applied == 1
    assert "key, key_split1 = jr.split(key)" in fixed
    assert lint_source(fixed, path="m.py", config=CFG).findings == []
    # bare from-import: no module path to borrow `split` from — the
    # finding is reported unfixable and the source left untouched
    src2 = textwrap.dedent("""
        from jax.random import normal, uniform


        def sample(key):
            a = normal(key, (4,))
            b = uniform(key, (4,))
            return a + b
    """)
    fixed2, res2 = fix_source(src2, "m.py")
    assert fixed2 == src2 and not res2.changed and res2.unfixable == 1


def test_fix_dtx004_clean_split_idiom_untouched():
    src = textwrap.dedent("""
        import jax


        def sample(key):
            key, sub = jax.random.split(key)
            a = jax.random.normal(sub, (4,))
            b = jax.random.uniform(key, (4,))
            return a + b
    """)
    fixed, res = fix_source(src, "m.py")
    assert fixed == src and not res.changed and res.applied == 0


def test_apply_edits_adjacent_ok_overlap_refused():
    assert apply_edits("abcdef", [SpanEdit(0, 2, "X"),
                                  SpanEdit(2, 4, "Y")]) == "XYef"
    with pytest.raises(OverlapError):
        apply_edits("abcdef", [SpanEdit(0, 3, "X"), SpanEdit(2, 4, "Y")])
    with pytest.raises(OverlapError):
        apply_edits("ab", [SpanEdit(1, 5, "X")])  # out of range


def test_cli_fix_check_then_fix_then_check_clean(tmp_path, capsys):
    p = tmp_path / "m.py"
    src = ("import jax.numpy as jnp\n"
           "def f(x, fill=jnp.zeros((4,))):\n"
           "    return x + fill\n")
    p.write_text(src)
    common = ["--no-config", "--no-baseline", "--no-cache"]
    # --check: reports, exits 1, WRITES NOTHING
    assert dtxlint_main([str(p), "--fix", "--check"] + common) == 1
    assert p.read_text() == src
    # --fix: applies, re-lints clean
    assert dtxlint_main([str(p), "--fix"] + common) == 0
    assert "fill=None" in p.read_text()
    # CI idempotency gate is now green
    assert dtxlint_main([str(p), "--fix", "--check"] + common) == 0
    capsys.readouterr()


# ------------------------------------------------------------- CLI additions
def test_cli_changed_lints_only_files_differing_from_head(tmp_path, capsys,
                                                          monkeypatch):
    monkeypatch.chdir(tmp_path)
    git = ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
    subprocess.run(["git", "init", "-q"], check=True)
    clean = tmp_path / "clean.py"
    clean.write_text("def f():\n    return 1\n")
    # stale.py carries a finding but will be UNCHANGED vs HEAD
    (tmp_path / "stale.py").write_text(
        "import jax.numpy as jnp\nA = jnp.ones((2,))\n")
    subprocess.run(["git", "add", "-A"], check=True)
    subprocess.run(git + ["commit", "-qm", "base"], check=True)

    common = ["--changed", "--no-config", "--no-baseline", "--no-cache"]
    assert dtxlint_main([str(tmp_path)] + common) == 0
    assert "no changed python files" in capsys.readouterr().out

    clean.write_text("import jax.numpy as jnp\nB = jnp.ones((3,))\n")
    assert dtxlint_main([str(tmp_path)] + common) == 1
    out = capsys.readouterr().out
    assert "clean.py" in out and "stale.py" not in out

    # git prints toplevel-relative paths: invoking from a SUBDIRECTORY must
    # still resolve them (the pre-commit shape — a silently-empty run here
    # green-lights dirty code)
    sub = tmp_path / "sub"
    sub.mkdir()
    monkeypatch.chdir(sub)
    assert dtxlint_main([str(tmp_path)] + common) == 1
    assert "clean.py" in capsys.readouterr().out

    # brand-NEW (untracked) files are the most common pre-commit case and
    # never show in `git diff HEAD` — they must still be linted
    monkeypatch.chdir(tmp_path)
    subprocess.run(["git", "add", "-A"], check=True)
    subprocess.run(git + ["commit", "-qm", "wip"], check=True)
    (tmp_path / "fresh.py").write_text(
        "import jax.numpy as jnp\nC = jnp.ones((4,))\n")
    assert dtxlint_main([str(tmp_path)] + common) == 1
    assert "fresh.py" in capsys.readouterr().out


def test_cli_format_json_holds_on_early_exit_paths(tmp_path, capsys,
                                                   monkeypatch):
    # the documented stdout contract (--format json → one schema-versioned
    # object) must hold on the --changed-empty and --fix --check paths too
    monkeypatch.chdir(tmp_path)
    git = ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
    subprocess.run(["git", "init", "-q"], check=True)
    p = tmp_path / "m.py"
    p.write_text("def f():\n    return 1\n")
    subprocess.run(["git", "add", "-A"], check=True)
    subprocess.run(git + ["commit", "-qm", "base"], check=True)

    common = ["--no-config", "--no-baseline", "--no-cache", "--format",
              "json"]
    assert dtxlint_main([str(tmp_path), "--changed"] + common) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == 2 and doc["findings"] == [] and not doc["failed"]

    p.write_text("import jax\n\nfor i in range(2):\n    g = jax.jit(f)\n"
                 "    g(i)\n")
    assert dtxlint_main([str(p), "--fix", "--check"] + common) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["failed"] and doc["fix"]["fixed"] == 1 \
        and doc["would_change"] == ["m.py"]  # display-path convention


def test_fix_dtx008_docstring_only_body_keeps_docstring():
    src = textwrap.dedent("""
    import jax.numpy as jnp


    def pad(x, fill=jnp.zeros((4,))):
        \"\"\"Docstring must stay first.\"\"\"
    """).lstrip()
    fixed, res = fix_source(src, "m.py", config=LintConfig())
    assert res.applied == 1
    mod = ast.parse(fixed)
    fn = mod.body[-1]
    assert ast.get_docstring(fn) == "Docstring must stay first."
    assert "if fill is None:" in fixed


def test_per_file_disable_is_config_level_not_suppression():
    cfg = LintConfig(per_file_disable=("*/generated/*.py:DTX008",
                                       "legacy_*.py:all"))
    src = "import jax.numpy as jnp\nA = jnp.ones((2,))\n"
    res = lint_source(src, path="pkg/generated/tables.py", config=cfg)
    assert res.findings == [] and res.suppressed == 0
    assert lint_source(src, path="legacy_x.py", config=cfg).findings == []
    kept = lint_source(src, path="pkg/other.py", config=cfg)
    assert [f.rule for f in kept.findings] == ["DTX008"]


# --------------------------------------------------------- cache and budget
def test_program_cache_reuse_and_repo_lint_budget(tmp_path):
    cfg = dataclasses.replace(load_config("."),
                              cache=str(tmp_path / "cache.json"))
    t0 = time.process_time()
    cold_res, cold_stats = lint_program(["datatunerx_tpu"], config=cfg)
    cold = time.process_time() - t0
    assert cold_stats.analyzed == cold_stats.files > 0

    t0 = time.process_time()
    warm_res, warm_stats = lint_program(["datatunerx_tpu"], config=cfg)
    warm = time.process_time() - t0
    assert warm_stats.reused == warm_stats.files == cold_stats.files
    assert warm_stats.analyzed == 0
    assert ([f.render() for f in warm_res.findings]
            == [f.render() for f in cold_res.findings])
    # the cached run is materially cheaper (locally ~6s cold vs ~0.1s warm),
    # judged on this process's own CPU time: the wall clock of a worker
    # among six busy ones is no budget (it read 10 s at PR 43)
    assert warm < cold / 2, f"cache not materially cheaper ({warm:.2f}s of {cold:.2f}s)"


# ------------------------------------------------------- framework behavior
def test_inline_suppression_comment_silences_one_rule():
    src = """
    import jax.numpy as jnp

    A = jnp.ones((2,))  # dtxlint: disable=DTX008 -- frozen table, deliberate
    B = jnp.ones((2,))  # dtxlint: disable=DTX001
    C = jnp.ones((2,))  # dtxlint: disable=all
    """
    res = run(src)
    assert [f.rule for f in res.findings] == ["DTX008"]  # only B still fires
    assert res.suppressed == 2


def test_baseline_roundtrip_and_partition(tmp_path):
    res = run("import jax.numpy as jnp\nA = jnp.ones((2,))\n")
    assert len(res.findings) == 1
    path = tmp_path / "baseline.json"
    save_baseline(str(path), res.findings)
    carried = load_baseline(str(path))
    new, baselined = partition(res.findings, carried)
    assert new == [] and len(baselined) == 1
    # a second, identical finding needs a second baseline entry
    two = res.findings * 2
    new, baselined = partition(two, carried)
    assert len(new) == 1 and len(baselined) == 1
    assert load_baseline(str(tmp_path / "missing.json")) == {}


def test_cli_json_output_and_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\nA = jnp.ones((2,))\n")
    rc = dtxlint_main([str(bad), "--format", "json", "--no-config",
                       "--no-baseline", "--no-cache"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["failed"]
    assert doc["version"] == 2  # schema version for CI annotation tooling
    assert doc["cache"] == {"analyzed": 1, "reused": 0}
    assert doc["findings"][0]["rule"] == "DTX008"
    assert doc["findings"][0]["line"] == 2

    good = tmp_path / "good.py"
    good.write_text("def f():\n    return 1\n")
    assert dtxlint_main([str(good), "--no-config", "--no-baseline",
                         "--no-cache"]) == 0


def test_cli_write_baseline_then_clean(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\nA = jnp.ones((2,))\n")
    base = tmp_path / "base.json"
    assert dtxlint_main([str(bad), "--no-config", "--no-cache", "--baseline",
                         str(base), "--write-baseline"]) == 0
    assert dtxlint_main([str(bad), "--no-config", "--no-cache", "--baseline",
                         str(base)]) == 0
    capsys.readouterr()


def test_select_runs_only_named_rules(tmp_path):
    src = ("import jax\nimport jax.numpy as jnp\n"
           "A = jnp.ones((2,))\n"
           "def f(key):\n"
           "    a = jax.random.normal(key, (2,))\n"
           "    return a + jax.random.uniform(key, (2,))\n")
    p = tmp_path / "m.py"
    p.write_text(src)
    res = lint_paths([str(p)], config=LintConfig())
    assert {f.rule for f in res.findings} == {"DTX004", "DTX008"}
    from datatunerx_tpu.analysis.rules import rules_by_id

    res = lint_paths([str(p)], config=LintConfig(),
                     rules=rules_by_id(["DTX004"]))
    assert {f.rule for f in res.findings} == {"DTX004"}


def test_config_disable_and_toml_subset(tmp_path):
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [project]
        name = "x"

        [tool.dtxlint]
        baseline = "b.json"
        disable = ["DTX008"]
        hot-functions = [
            "train_step",
            "hot_*",
        ]
        mesh-axes = ["dp", "tp"]
    """))
    cfg = load_config(str(tmp_path))
    assert cfg.baseline == "b.json"
    assert cfg.disable == ("DTX008",)
    assert cfg.hot_functions == ("train_step", "hot_*")
    assert cfg.mesh_axes == ("dp", "tp")
    res = lint_source("import jax.numpy as jnp\nA = jnp.ones((2,))\n",
                      config=cfg)
    assert res.findings == []  # DTX008 disabled by config


def test_syntax_error_reports_dtx000_not_crash():
    res = lint_source("def broken(:\n", path="x.py")
    assert [f.rule for f in res.findings] == ["DTX000"]


# --------------------------------------------------------------- CI contract
def test_repo_lints_clean_at_head():
    """The acceptance gate: the shipped tree has zero non-suppressed
    findings against the shipped (empty-findings) baseline — with the
    cross-module program pass ON, over the same surface CI lints."""
    cfg = dataclasses.replace(load_config("."), cache="")
    res, _ = lint_program(
        ["datatunerx_tpu", "scripts", "__graft_entry__.py"],
        config=cfg)
    baseline = load_baseline(cfg.resolve(cfg.baseline))
    new, _ = partition(res.findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)
    assert baseline == {}, "policy: the baseline stays empty"


def test_mesh_axes_extracted_from_mesh_module():
    from datatunerx_tpu.analysis.config import mesh_axes_for

    cfg = load_config(".")
    assert set(mesh_axes_for(cfg)) == {"dp", "fsdp", "tp", "sp"}
