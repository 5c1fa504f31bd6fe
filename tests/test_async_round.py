"""Buffered-async round engine, fleet controller and cut planner.

Pins the subsystem's load-bearing contracts:

* staleness weight w(τ=0) is exactly 1.0, so a single full-cohort flush
  of :class:`AsyncReplayServer` is BIT-EXACT against the synchronous
  :func:`seed_replay_aggregate`, and the
  whole ``make_async_round`` at ``buffer_k=0`` is bit-exact against
  ``make_fed_round(uplink="seed_replay")`` — client AND server params;
* masked/dropped clients contribute nothing regardless of arrival
  order (property test over permutations and mask patterns);
* buffered mode really snapshots mid-round and later arrivals carry
  genuine staleness τ > 0;
* the cut planner's compiled-HLO costs grow with cut depth and the
  plan picks the deepest cut that fits the device profile;
* the controller retries faulting clients with bounded backoff,
  discards dropped clients' in-flight results, and records staleness
  across versions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregate as AG
from repro.core import protocols as P
from repro.core import zo as Z
from repro.fed import (AsyncReplayServer, FleetController, StalenessConfig,
                       candidate_costs, plan_cut, staleness_weight)
from repro.fed.cutplan import CutPlan, DeviceProfile
from repro.kernels import ops as O


def make_params():
    return {"w": jnp.ones((6, 3)), "b": {"c": jnp.linspace(-1.0, 1.0, 5)}}


def assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# staleness weight
# ---------------------------------------------------------------------------

def test_staleness_weight_properties():
    for alpha in (0.0, 0.5, 1.0, 3.0):
        assert staleness_weight(0, alpha) == 1.0       # exact: bit-exact
    for tau in (0, 1, 5, 100):                         # sync limit
        assert staleness_weight(tau, 0.0) == 1.0
    assert staleness_weight(1, 1.0) == 0.5
    ws = [staleness_weight(t, 0.5) for t in range(6)]
    assert all(a > b for a, b in zip(ws, ws[1:]))      # monotone decay
    assert all(0.0 < w <= 1.0 for w in ws)
    assert StalenessConfig(alpha=2.0).weight(1) == 0.25


# ---------------------------------------------------------------------------
# bit-exactness vs the synchronous aggregator
# ---------------------------------------------------------------------------

def test_single_flush_bit_exact_kernel():
    """One full-cohort flush at w(τ)=1 == seed_replay_aggregate, byte
    for byte, regardless of the order arrivals were submitted in."""
    params = make_params()
    n, h, pairs, lr = 4, 2, 2, 1e-2
    seeds = O.fold_seed(jnp.int32(9), jnp.arange(n))
    coeffs = jax.random.normal(jax.random.PRNGKey(5), (n, h, pairs))
    mask = jnp.array([1.0, 0.0, 1.0, 1.0])
    ref = AG.seed_replay_aggregate(params, seeds, coeffs, lr, mask)

    srv = AsyncReplayServer(params, lr)
    sh = np.asarray(seeds)
    for cid in (2, 0, 3, 1):                      # scrambled arrivals
        srv.submit(cid, sh[cid], coeffs[cid], mask=float(mask[cid]))
    assert srv.version == 0                       # buffer_k=0: no auto
    srv.flush()
    assert srv.version == 1
    assert_trees_equal(ref, srv.params)
    assert srv.telemetry.dropped == 1             # the masked client


def test_async_round_bit_exact_vs_sync_at_buffer0():
    """make_async_round(buffer_k=0, alpha=0) == make_fed_round(uplink=
    'seed_replay') byte-for-byte on client AND server state, with
    stragglers masked in both."""
    from repro.data.pipeline import round_batches
    from repro.data.synthetic import GaussianMixtureImages
    from repro.models import cnn as CNN
    from repro.optim.optimizers import make_optimizer

    cfg = CNN.CNNConfig(widths=(8, 16), blocks_per_stage=1, classes=4,
                        client_blocks=1)
    ds = GaussianMixtureImages(classes=4, hw=8, noise=0.5)
    api = P.cnn_api(cfg)
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    lr = 2e-2
    zo = Z.ZOConfig(mu=1e-3, n_pairs=2)
    fed = P.FedConfig(n_clients=4, h=2, straggler_prob=0.4)
    copt = make_optimizer("zo_sgd", lr)
    sopt = make_optimizer("adamw", 2e-3)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    rb = round_batches(ds, jax.random.PRNGKey(3), 4, 2, 8)
    key = jax.random.PRNGKey(9)

    sync = P.make_fed_round(api, "heron", zo, fed, copt, sopt,
                            uplink="seed_replay", client_lr=lr)
    s_sync, m_sync = sync(state, rb, key)
    anyc = P.make_async_round(api, "heron", zo, fed, copt, sopt,
                              client_lr=lr)
    # arrival order is durations-driven and must not matter at buffer_k=0
    s_async, m_async = anyc(state, rb, key,
                            durations=[3.0, 1.0, 4.0, 2.0])
    assert_trees_equal(s_sync["client"], s_async["client"])
    assert_trees_equal(s_sync["server"], s_async["server"])
    # the scalar metric reduces over a different stacking layout, so it
    # is allclose (1-ulp) rather than byte-equal on multi-device hosts
    np.testing.assert_allclose(np.asarray(m_sync["server_loss"]),
                               np.asarray(m_async["server_loss"]),
                               rtol=1e-6)
    assert m_async["flushes"] == 1.0
    assert m_async["mean_staleness"] == 0.0
    assert m_async["sim_makespan_s"] == 4.0


def test_buffered_flushes_carry_staleness():
    from repro.data.pipeline import round_batches
    from repro.data.synthetic import GaussianMixtureImages
    from repro.models import cnn as CNN
    from repro.optim.optimizers import make_optimizer

    cfg = CNN.CNNConfig(widths=(8, 16), blocks_per_stage=1, classes=4,
                        client_blocks=1)
    ds = GaussianMixtureImages(classes=4, hw=8, noise=0.5)
    api = P.cnn_api(cfg)
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    sopt = make_optimizer("adamw", 2e-3)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    rnd = P.make_async_round(
        api, "heron", Z.ZOConfig(mu=1e-3, n_pairs=1),
        P.FedConfig(n_clients=4, h=2), make_optimizer("zo_sgd", 2e-2),
        sopt, client_lr=2e-2, staleness_alpha=0.5, buffer_k=2)
    rb = round_batches(ds, jax.random.PRNGKey(3), 4, 2, 8)
    _, m = rnd(state, rb, jax.random.PRNGKey(9),
               durations=[1.0, 1.0, 10.0, 1.0])
    assert m["flushes"] == 2.0                 # mid-round snapshot
    assert m["mean_staleness"] > 0.0           # straggler flushed at τ=1
    assert m["time_to_first_update_s"] == 1.0  # before the straggler
    assert m["sim_makespan_s"] == 10.0
    assert m["updates_per_sim_s"] > 1.0 / 10.0  # beats the barrier


# ---------------------------------------------------------------------------
# masked / dropped clients: nothing, in any order (property)
# ---------------------------------------------------------------------------

def test_masked_clients_contribute_nothing_any_order():
    """Exhaustive property sweep (all 2^n mask patterns x arrival
    permutations x buffer sizes): a masked/dropped client contributes
    NOTHING — poisoning its coefficients is a byte-exact no-op — and at
    buffer_k=0 the arrival order itself is irrelevant.  (Deterministic
    enumeration instead of hypothesis: exhaustive over masks, and the
    container may not ship hypothesis.)"""
    import itertools

    params = make_params()
    n, h, pairs, lr = 4, 1, 2, 1e-2
    seeds = np.asarray(O.fold_seed(jnp.int32(0), jnp.arange(n)))
    coeffs = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (n, h, pairs)))
    orders = [(0, 1, 2, 3), (3, 2, 1, 0), (2, 0, 3, 1)]

    def run(order, mask, cfs, buffer_k):
        srv = AsyncReplayServer(params, lr,
                                staleness=StalenessConfig(alpha=0.7),
                                buffer_k=buffer_k)
        for cid in order:
            srv.submit(cid, seeds[cid], cfs[cid], mask=mask[cid])
        srv.flush()
        return srv.params

    for mask in itertools.product([0.0, 1.0], repeat=n):
        poisoned = coeffs.copy()
        for cid in range(n):
            if mask[cid] == 0.0:
                poisoned[cid] = 1e6
        for buffer_k in (0, 3):
            for order in orders:
                out = run(order, mask, coeffs, buffer_k)
                out_p = run(order, mask, poisoned, buffer_k)
                assert_trees_equal(out, out_p)
                if buffer_k == 0:
                    assert_trees_equal(
                        out, run(range(n), mask, coeffs, 0))


# ---------------------------------------------------------------------------
# cut planner
# ---------------------------------------------------------------------------

def _cnn_costs():
    from repro.data.synthetic import GaussianMixtureImages
    from repro.models import cnn as CNN

    cfg = CNN.CNNConfig(widths=(8, 16), blocks_per_stage=2, classes=4,
                        client_blocks=1)
    ds = GaussianMixtureImages(classes=4, hw=8, noise=0.5)
    return candidate_costs(cfg, ds.batch(jax.random.PRNGKey(2), 8))


def test_cutplan_costs_grow_with_depth():
    costs = _cnn_costs()
    assert [c.cut for c in costs] == [1, 2, 3]
    pb = [c.param_bytes for c in costs]
    fl = [c.flops for c in costs]
    by = [c.bytes for c in costs]
    assert all(a < b for a, b in zip(pb, pb[1:]))   # deeper = more params
    assert all(a < b for a, b in zip(by, by[1:]))   # and more traffic
    assert all(a <= b for a, b in zip(fl, fl[1:]))


def test_cutplan_picks_deepest_feasible():
    costs = _cnn_costs()
    rich = DeviceProfile("rich", peak_flops=1e12, mem_bw=1e11,
                         mem_bytes=1e12)
    plan = plan_cut(costs, rich, h=2, n_pairs=2)
    assert plan.cut == 3 and plan.feasible
    # memory budget binds: only the shallowest cut's params fit
    tight = DeviceProfile("tight", peak_flops=1e12, mem_bw=1e11,
                          mem_bytes=float(costs[0].param_bytes))
    plan = plan_cut(costs, tight, h=2, n_pairs=2)
    assert plan.cut == 1 and plan.feasible
    # deadline binds: pick a deadline between cut-1 and cut-3 round time
    from repro.fed.cutplan import round_time_s
    slow = DeviceProfile("slow", peak_flops=1e6, mem_bw=1e6,
                         mem_bytes=1e12,
                         deadline_s=round_time_s(costs[0], DeviceProfile(
                             "slow", 1e6, 1e6, 1e12), 2, 2) * 1.5)
    plan = plan_cut(costs, slow, h=2, n_pairs=2)
    assert plan.cut < 3
    # nothing fits: shallowest cut, flagged infeasible
    broke = DeviceProfile("broke", peak_flops=1e12, mem_bw=1e11,
                          mem_bytes=1.0)
    plan = plan_cut(costs, broke, h=2, n_pairs=2)
    assert plan.cut == 1 and not plan.feasible


# ---------------------------------------------------------------------------
# fleet controller
# ---------------------------------------------------------------------------

def _tiny_fleet(injector=None, buffer_k=0, alpha=0.0):
    params = make_params()
    h, pairs, lr = 1, 2, 1e-2
    srv = AsyncReplayServer(params, lr, buffer_k=buffer_k,
                            staleness=StalenessConfig(alpha=alpha))

    def local_fn(global_params, cid, round_idx, base_version):
        ck = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(7), round_idx), cid)
        coeffs = jax.random.normal(ck, (h, pairs))
        return Z.seed_from_key(ck), coeffs, 1.0

    ctl = FleetController(srv, local_fn, injector=injector,
                          sleep=lambda s: None, max_retries=2)
    return srv, ctl


def test_controller_fault_drill_retries_with_backoff():
    from repro.distributed.fault import FaultInjector

    srv, ctl = _tiny_fleet(injector=FaultInjector(fail_at=(1,)))
    prof = DeviceProfile("d", 1e9, 1e9, 1e9)
    for d in (1.0, 2.0):
        ctl.admit(prof, CutPlan(cut=1, round_s=d, feasible=True))
    assert ctl.run(4) == 4
    t = ctl.telemetry
    assert t.restarts == 1                 # one injected fault, retried
    assert t.backoff_total_s > 0.0
    assert t.completed == 4 and t.dropped == 0
    assert srv.telemetry.arrivals == 4


def test_controller_gives_up_and_drops_permanent_faulter():
    class AlwaysFail:
        def check(self, step):
            raise RuntimeError("dead device")

    srv, ctl = _tiny_fleet(injector=AlwaysFail())
    prof = DeviceProfile("d", 1e9, 1e9, 1e9)
    ctl.admit(prof, CutPlan(cut=1, round_s=1.0, feasible=True))
    assert ctl.run(1) == 0                 # heap drains, nothing lands
    t = ctl.telemetry
    assert t.restarts == ctl.max_retries + 1
    assert t.dropped == 1
    assert srv.telemetry.arrivals == 0


def test_controller_discards_dropped_clients_inflight_result():
    srv, ctl = _tiny_fleet()
    prof = DeviceProfile("d", 1e9, 1e9, 1e9)
    fast = ctl.admit(prof, CutPlan(cut=1, round_s=1.0, feasible=True))
    slow = ctl.admit(prof, CutPlan(cut=1, round_s=50.0, feasible=True))
    ctl.run(2, redispatch=False)           # both first rounds land
    before = srv.telemetry.arrivals
    ctl._dispatch(ctl.clients[slow], ctl.now)
    ctl.drop(slow)                         # leaves while in flight
    ctl.run(1, redispatch=False)           # its result surfaces...
    assert ctl.telemetry.discarded == 1    # ...and is discarded
    assert srv.telemetry.arrivals == before
    assert ctl.clients[fast].active and not ctl.clients[slow].active


def test_controller_staleness_across_versions():
    """Buffered flushes advance the global version while slower clients
    are in flight, so their arrivals carry τ > 0."""
    srv, ctl = _tiny_fleet(buffer_k=2, alpha=0.5)
    prof = DeviceProfile("d", 1e9, 1e9, 1e9)
    for d in (1.0, 1.0, 30.0):
        ctl.admit(prof, CutPlan(cut=1, round_s=d, feasible=True))
    ctl.run(5)        # fast pair flushes at least twice before t=30
    assert srv.version >= 2
    ctl.run(1)        # the slow client lands with base_version 0
    srv.flush()
    assert srv.telemetry.staleness_sum > 0.0
    assert ctl.telemetry.remeshes == 3     # one per admission


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_async_validation():
    from repro.optim.optimizers import make_optimizer
    sopt = make_optimizer("adamw", 1e-3)
    with pytest.raises(ValueError, match="heron"):
        P.make_async_round(None, "cse_fsl", Z.ZOConfig(),
                           P.FedConfig(n_clients=2, h=1),
                           make_optimizer("adamw", 1e-3), sopt,
                           client_lr=1e-2)
    with pytest.raises(ValueError, match="client_lr"):
        P.make_async_round(None, "heron", Z.ZOConfig(),
                           P.FedConfig(n_clients=2, h=1),
                           make_optimizer("zo_sgd", 1e-2), sopt,
                           client_lr=None)
