"""Test harness: force an 8-device virtual CPU mesh before backend init.

Mirrors the reference's testing stance (SURVEY.md section 4): executor tests
run against in-memory fakes; multi-chip sharding is validated on virtual CPU
devices (`--xla_force_host_platform_device_count=8`) — JAX-on-CPU stands in
for the TPU mesh. The chip itself is only ever driven by `chip_smoke.py`
(and, for the compiler alone, by tests/test_tpu_compile.py against a
described topology).

`JAX_PLATFORMS` is set here, before `jax` is first imported, and that is
enough: no plug-in of this suite imports jax before conftest does.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent compilation cache: the kernel tests compile many
# (capacity, width, chunk) shape buckets; without a disk cache every
# pytest invocation recompiles all of them from scratch. Placed like
# every other entry point's: $JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache.
from risingwave_tpu.utils.jaxtools import (  # noqa: E402
    enable_compilation_cache,
)

enable_compilation_cache()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _strict_plan_checker():
    """Assert-don't-fallback mode for the plan-rewrite checker
    (frontend/opt): a rewrite rule that breaks a plan invariant fails
    the suite loudly instead of silently falling back."""
    from risingwave_tpu.frontend.opt import set_strict_checker
    set_strict_checker(True)
    yield
    set_strict_checker(False)


@pytest.fixture(autouse=True)
def _strict_empty_chunks():
    """Assertion mode for the empty-message-suppression invariant: a
    MonitoredExecutor (i.e. any deployed chain) emitting a
    zero-visible-row chunk fails the test instead of just counting."""
    from risingwave_tpu.stream.monitor import set_strict_empty_chunks
    set_strict_empty_chunks(True)
    yield
    set_strict_empty_chunks(False)


@pytest.fixture(autouse=True)
def _strict_memory_accounting():
    """Tier-1 strict mode for the state-tier soft limit: a test that
    configures MemoryContext.soft_limit (directly or via SET
    state_tier_soft_limit_mb) fails if the accounted host-state bytes
    still exceed it at teardown — the tier's pressure sweeps must have
    brought the state back under the watermark. Tests that set no
    limit are untouched. The limit is process-global, so it always
    resets between tests."""
    from risingwave_tpu.utils import memory as _mem
    _mem.GLOBAL.soft_limit = None
    yield
    limit = _mem.GLOBAL.soft_limit
    if limit is None:
        return
    total = _mem.GLOBAL.total_bytes()
    _mem.GLOBAL.soft_limit = None
    assert total <= limit, (
        f"accounted host state {total}B exceeds the configured "
        f"state-tier soft limit {limit}B at teardown — pressure "
        f"eviction failed to bound it")


@pytest.fixture(autouse=True)
def _conservation_gate():
    """Tier-1 strict mode for the epoch phase ledger (utils/ledger.py):
    any steady-state epoch a test drives whose `unattributed` residual
    exceeds the conservation budget fails the test — the ledger can
    never silently rot. Warmup (compile-bearing), mutation and
    unmerged-distributed epochs are exempt; micro-epochs are below the
    gate's interval floor. Sits next to the RecompileGuard and
    DispatchBudget strict-mode guards."""
    from risingwave_tpu.utils import ledger as _ledger
    _ledger.LEDGER.clear()
    yield
    violations = _ledger.LEDGER.gate_violations()
    _ledger.LEDGER.clear()
    assert not violations, (
        "epoch phase ledger conservation gate (tier-1 strict mode): "
        "steady-state epochs carried unattributed wall-clock over "
        "budget — an uninstrumented stall crept into the barrier "
        "path. (epoch, interval_s, unattributed_s, coverage, domain): "
        f"{[(hex(e), round(i, 3), round(u, 3), c, d) for e, i, u, c, d in violations]}")


@pytest.fixture(autouse=True)
def _attribution_gate():
    """Tier-1 strict mode for serving-cost attribution (ISSUE 16):
    (a) the per-MV device-seconds split can redistribute the phase
    ledger's books but never mint time — Σ per-MV ≤ the domain's
    ledgered device_compute + ε for every sealed local epoch; (b) the
    per-(table, vnode) topology's incremental totals must agree with a
    full recount of the authoritative size map at every checkpoint
    (armed here; a no-op in production). Same arming pattern as the
    ledger conservation gate."""
    from risingwave_tpu.state import topology as _topology
    from risingwave_tpu.stream import costs as _costs
    from risingwave_tpu.stream import hotkeys as _hotkeys
    _costs.COSTS.clear()
    _topology.TOPOLOGY.clear()
    _hotkeys.HOTKEYS.clear()
    _topology.TOPOLOGY.arm_checkpoint_verify(True)
    yield
    split = _costs.COSTS.gate_violations()
    _topology.TOPOLOGY.checkpoint_verify()
    books = _topology.TOPOLOGY.gate_violations()
    _costs.COSTS.clear()
    _topology.TOPOLOGY.clear()
    _hotkeys.HOTKEYS.clear()
    _topology.TOPOLOGY.arm_checkpoint_verify(False)
    assert not split, (
        "per-MV attribution gate (tier-1 strict mode): the MV split "
        "claims more device time than the domain's phase ledger "
        "recorded — the owner split minted time. (epoch, domain, "
        "sum_mv_device_s, domain_device_s): "
        f"{[(hex(e), d, round(s, 4), round(g, 4)) for e, d, s, g in split[:5]]}")
    assert not books, (
        "state-topology recount gate (tier-1 strict mode): the "
        "incremental per-table totals disagree with a full recount of "
        "the authoritative size map — delta arithmetic drifted. "
        "(table_id, rows_inc, rows_true, bytes_inc, bytes_true): "
        f"{books[:5]}")


@pytest.fixture(autouse=True)
def _tricolor_freshness_gate():
    """Tier-1 strict mode for the utilization tricolor and per-MV
    freshness (stream/monitor.py + stream/freshness.py): every
    published busy/backpressure/idle triple must sum to ≤ 1.0 + ε
    (the three parts partition disjoint wall time by construction —
    an oversum is a double-count bug), and every resolved freshness
    sample must be finite and non-negative once the first frontier
    passes materialize. Same arming pattern as the ledger
    conservation gate."""
    from risingwave_tpu.stream import freshness as _fresh
    from risingwave_tpu.stream import monitor as _monitor
    from risingwave_tpu.stream.bottleneck import BOTTLENECKS
    _monitor.UTILIZATION.clear()
    _fresh.FRESHNESS.clear()
    BOTTLENECKS.clear()
    yield
    tri = _monitor.UTILIZATION.gate_violations()
    lag = _fresh.FRESHNESS.gate_violations()
    _monitor.UTILIZATION.clear()
    _fresh.FRESHNESS.clear()
    BOTTLENECKS.clear()
    assert not tri, (
        "utilization tricolor gate (tier-1 strict mode): published "
        "busy+backpressure+idle triples exceed 1.0 + ε — two states "
        "claim the same wall time. ((fragment, actor, node), "
        f"executor, epoch, busy, bp, idle): {tri[:5]}")
    assert not lag, (
        "freshness gate (tier-1 strict mode): per-MV lag samples "
        "must be finite and non-negative once the first frontier "
        f"passes materialize. (mv, epoch, lag, wall_lag): {lag[:5]}")


def _worker_children() -> list:
    """PIDs of live `risingwave_tpu.cluster.worker` subprocesses whose
    parent is this test process. Zombies (state Z) don't count — a
    corpse holds no ports; what this hunts is the LIVE leak that
    shadows a later test's exchange/control ports."""
    import os
    me = os.getpid()
    out = []
    if not os.path.isdir("/proc"):          # non-Linux: guard is off
        return out
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                tail = f.read().rsplit(")", 1)[1].split()
            state, ppid = tail[0], int(tail[1])
            if ppid != me or state == "Z":
                continue
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ")
            if b"risingwave_tpu.cluster.worker" in cmd:
                out.append(int(pid))
        except (OSError, ValueError, IndexError):
            continue
    return out


@pytest.fixture(autouse=True)
def _no_orphan_workers():
    """Tier-1 guard (ISSUE 8): a test that leaves worker subprocesses
    running fails loudly — a leaked `WorkerHandle` child keeps serving
    its old exchange/control ports and can shadow a later cluster
    test's connections with stale state. The guard also SIGKILLs the
    orphans so one broken test doesn't cascade."""
    import os
    import signal
    yield
    orphans = _worker_children()
    if orphans:
        for pid in orphans:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        pytest.fail(
            f"test leaked live worker subprocess(es) {orphans} — "
            "every WorkerHandle/Cluster must be stopped (they were "
            "killed now to protect the rest of the suite)")


@pytest.fixture(autouse=True)
def _no_orphan_sink_staging():
    """Tier-1 guard (ISSUE 20): a test that leaves staged-but-
    unmanifested sink segments behind fails loudly — uncommitted
    staging outliving its test is exactly the leakage the exactly-once
    protocol forbids (a converged pipeline either commits an epoch's
    segments or recovery truncates them). The guard also SWEEPS the
    orphans so a later test reusing the path can't promote a dead
    generation's rows."""
    from risingwave_tpu.connectors import sink as _sink
    _sink.reset_touched_roots()
    yield
    import os
    leaked = {}
    for root in _sink.touched_roots():
        if not os.path.isdir(root):
            continue                 # tmp_path already torn down
        from risingwave_tpu.storage.object_store import (
            LocalFsObjectStore,
        )
        target = _sink.EpochSegmentTarget(LocalFsObjectStore(root))
        orphans = target.uncommitted_epochs()
        if orphans:
            # sweep before failing: floor=-1 truncates everything
            # unmanifested, protecting the rest of the suite
            target.recover(-1)
            leaked[root] = sorted(hex(e) for e in orphans)
    _sink.reset_touched_roots()
    if leaked:
        pytest.fail(
            f"test leaked uncommitted sink staging {leaked} — every "
            "epoch-segment sink must converge (commit or truncate) "
            "before the test ends (orphans were swept now to protect "
            "the rest of the suite)")


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


class DispatchBudget:
    """Tier-1 strict-mode guard for fragment fusion (ISSUE 6): a fused
    pipeline must be at least as dispatch-dense as its unfused
    baseline. Usage in fused-vs-unfused tests:

        out_off, d_off, rpd_off = dispatch_budget.measure(run_unfused)
        out_on,  d_on,  rpd_on  = dispatch_budget.measure(run_fused)
        dispatch_budget.check(d_off, rpd_off, d_on, rpd_on)

    check() fails the test if the fused run's rows-per-dispatch fell
    below the unfused baseline's, or its dispatch count did not drop.

    Granularity note (ARCHITECTURE.md "Metrics attribution"): the
    unfused arm counts per-chunk dispatch REQUESTS (kernel.apply
    enqueues) while the fused arm counts real backlogged launches, so
    this guards the executor-level dispatch pressure the fusion
    removes, not a launch-for-launch comparison.
    """

    @staticmethod
    def totals():
        from risingwave_tpu.utils.metrics import STREAMING
        d = sum(v for _l, v in STREAMING.device_dispatch.series())
        r = sum(s for _l, _n, s in
                STREAMING.rows_per_dispatch.series())
        return float(d), float(r)

    def measure(self, fn):
        """(fn result, dispatches, rows/dispatch) over fn's run."""
        d0, r0 = self.totals()
        out = fn()
        d1, r1 = self.totals()
        d = d1 - d0
        return out, d, (r1 - r0) / max(d, 1.0)

    @staticmethod
    def check(d_unfused, rpd_unfused, d_fused, rpd_fused):
        assert d_fused < d_unfused, (
            f"fused pipeline dispatched {d_fused} times, unfused "
            f"baseline {d_unfused} — fusion must strictly drop the "
            "device dispatch count")
        assert rpd_fused >= rpd_unfused, (
            f"fused rows-per-dispatch {rpd_fused:.1f} fell below the "
            f"unfused baseline {rpd_unfused:.1f} — dispatch-budget "
            "guard (tier-1 strict mode)")

    @staticmethod
    def check_ceiling(d_fused, d_baseline, what="baseline"):
        """Join-query extension (ISSUE 9): a fused join run must not
        exceed its comparison arm's dispatch count."""
        assert d_fused <= d_baseline, (
            f"fused join run dispatched {d_fused} times, {what} "
            f"{d_baseline} — dispatch-budget guard (tier-1 strict "
            "mode, join extension)")

    @staticmethod
    def sharded_totals():
        """(dispatches, rows) of the SHARDED kernels alone — counted
        at their real shard_map launch sites under kernel="sharded_*"
        labels (ISSUE 10 observability satellite)."""
        from risingwave_tpu.utils.metrics import STREAMING
        d = sum(v for l, v in STREAMING.device_dispatch.series()
                if l.get("kernel", "").startswith("sharded"))
        r = sum(s for l, _n, s in
                STREAMING.rows_per_dispatch.series()
                if l.get("kernel", "").startswith("sharded"))
        return float(d), float(r)

    def measure_sharded(self, fn):
        """(fn result, sharded dispatches, sharded rows/dispatch)."""
        d0, r0 = self.sharded_totals()
        out = fn()
        d1, r1 = self.sharded_totals()
        d = d1 - d0
        return out, d, (r1 - r0) / max(d, 1.0)

    @staticmethod
    def check_epoch_ceiling(dispatches, n_epochs, per_epoch,
                            what="sharded epoch batching"):
        """Distributed/sharded extension (ISSUE 10): SPMD dispatches
        per epoch must stay O(1) per kernel — `per_epoch` is the
        kernel count times its per-epoch dispatch budget (join: 2
        apply + 2 probe; agg: 1 step + 1 gather), NOT a per-chunk
        allowance. A regression back to per-chunk dispatch trips this
        immediately."""
        assert dispatches <= n_epochs * per_epoch, (
            f"{what}: {dispatches} sharded SPMD dispatches over "
            f"{n_epochs} epochs exceeds the O(1)-per-epoch ceiling "
            f"({per_epoch}/epoch) — the per-epoch discipline "
            "regressed to per-chunk dispatch (tier-1 strict mode)")


@pytest.fixture
def dispatch_budget():
    return DispatchBudget()


class RecompileGuard:
    """Tier-1 strict-mode guard for jitted-kernel shape stability
    (ISSUE 7): a steady-state run must not retrace kernels after
    warmup — a retrace on the hot path is a silent shape-churn
    regression (each costs a trace plus a compile).
    Usage:

        out, n_warm = recompile_guard.measure(run_warmup)
        out, n_steady = recompile_guard.measure(run_steady_state)
        recompile_guard.check_steady(n_steady)

    measure() counts stream_kernel_recompile_count growth over fn;
    check_steady() fails the test on ANY steady-state retrace.
    """

    @staticmethod
    def total():
        from risingwave_tpu.utils.metrics import STREAMING
        return sum(v for _l, v in
                   STREAMING.kernel_recompile.series())

    def measure(self, fn):
        t0 = self.total()
        out = fn()
        return out, self.total() - t0

    @staticmethod
    def check_steady(n_recompiles, what="steady state"):
        assert n_recompiles == 0, (
            f"{n_recompiles} jitted-kernel retraces during {what} — "
            "warmup must have compiled every shape bucket; a "
            "steady-state retrace is a shape-churn regression "
            "(recompile-guard, tier-1 strict mode)")


@pytest.fixture
def recompile_guard():
    return RecompileGuard()
