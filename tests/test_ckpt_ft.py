"""Checkpoint runtime + fault-tolerance tests: atomicity, corruption
fallback, buddy recovery, compression, bit-exact resume, elasticity,
watchdog, energy accounting."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import (ShardedStore, StoreConfig, CheckpointManager,
                        ManagerConfig)
from repro.configs import get_config, reduced
from repro.core.failures import get_process
from repro.core.policy import CheckpointPolicy, PolicyConfig
from repro.data import for_arch
from repro.energy import EnergyMeter, Phase, PAPER_EXASCALE_PROFILE
from repro.ft import (FailureInjector, FailureModel, FaultTolerantTrainer,
                      TrainerConfig, StepTimeWatchdog, plan_reshard)
from repro.models import build
from repro.optim import adamw

PW = PAPER_EXASCALE_PROFILE.power_params()


def small_tree(seed=0):
    k = jax.random.key(seed)
    return {"a": jax.random.normal(k, (128, 64)),
            "nested": {"b": jnp.arange(10, dtype=jnp.int32),
                       "c": jax.random.normal(k, (4096, 32))}}


# ---------------------------------------------------------------------------
# Store
# ---------------------------------------------------------------------------

class TestStore:
    def test_roundtrip(self, tmp_path):
        store = ShardedStore(StoreConfig(root=str(tmp_path)))
        tree = small_tree()
        store.save(5, tree)
        out, step = store.restore(tree)
        assert step == 5
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_retention_gc(self, tmp_path):
        store = ShardedStore(StoreConfig(root=str(tmp_path), retain=2))
        tree = small_tree()
        for s in (1, 2, 3, 4):
            store.save(s, tree)
        gens = [g.name for g in store.generations()]
        assert gens == ["step_000000003", "step_000000004"]

    def test_corruption_falls_back_one_generation(self, tmp_path):
        store = ShardedStore(StoreConfig(root=str(tmp_path)))
        t1 = small_tree(1)
        t2 = small_tree(2)
        store.save(1, t1)
        store.save(2, t2)
        # corrupt the newest shard
        newest = store.generations()[-1]
        shard = next(newest.glob("shard_*.npz"))
        data = bytearray(shard.read_bytes())
        data[100] ^= 0xFF
        shard.write_bytes(bytes(data))
        out, step = store.restore(t1)
        assert step == 1          # fell back
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(t1["a"]))

    def test_torn_write_no_manifest_is_invisible(self, tmp_path):
        store = ShardedStore(StoreConfig(root=str(tmp_path)))
        tree = small_tree()
        store.save(1, tree)
        # simulate a torn write: shard present, manifest missing
        torn = tmp_path / "step_000000009"
        torn.mkdir()
        (torn / "shard_00000.npz").write_bytes(b"garbage")
        out, step = store.restore(tree)
        assert step == 1

    def test_compressed_checkpoint_smaller_and_close(self, tmp_path):
        plain = ShardedStore(StoreConfig(root=str(tmp_path / "p")))
        comp = ShardedStore(StoreConfig(root=str(tmp_path / "c"),
                                        compress=True))
        tree = {"w": jax.random.normal(jax.random.key(0), (512, 512))}
        m1 = plain.save(1, tree)
        m2 = comp.save(1, tree)
        assert m2["bytes"] < 0.4 * m1["bytes"]
        out, _ = comp.restore(tree)
        rel = float(jnp.max(jnp.abs(out["w"] - tree["w"]))
                    / jnp.max(jnp.abs(tree["w"])))
        assert rel < 0.01

    def test_compression_keeps_adamw_second_moment_exact(self, tmp_path):
        """AdamW's ``v`` is restored bit for bit (an entry rounded to zero
        would divide the next update by ~eps); ``m`` is compressed."""
        from repro.optim import adamw
        params = {"w": jax.random.normal(jax.random.key(0), (128, 128))}
        st = adamw.init_state(params)
        v = jnp.exp(jax.random.normal(jax.random.key(1), (128, 128)) * 8)
        st = st._replace(m={"w": params["w"]}, v={"w": v})
        comp = ShardedStore(StoreConfig(root=str(tmp_path), compress=True))
        comp.save(1, (params, st))
        (_, out), _ = comp.restore((params, st))
        np.testing.assert_array_equal(np.asarray(out.v["w"]), np.asarray(v))
        assert not np.array_equal(np.asarray(out.m["w"]),
                                  np.asarray(params["w"]))

    def test_restore_empty_store(self, tmp_path):
        store = ShardedStore(StoreConfig(root=str(tmp_path)))
        out, step = store.restore(small_tree())
        assert out is None and step is None


# ---------------------------------------------------------------------------
# Manager (async, buddy, policy-driven cadence)
# ---------------------------------------------------------------------------

def _policy(strategy="fixed", period=10.0, **kw):
    return CheckpointPolicy(PolicyConfig(strategy=strategy,
                                         fixed_period_s=period, **kw), PW)


class TestManager:
    def test_async_checkpoint_and_restore(self, tmp_path):
        pol = _policy()
        mgr = CheckpointManager(ShardedStore(StoreConfig(str(tmp_path))),
                                pol)
        tree = small_tree()
        mgr.checkpoint(3, tree)
        mgr.wait()
        out, step, source = mgr.restore(tree)
        assert step == 3 and source == "store"

    def test_buddy_recovery_when_store_lost(self, tmp_path):
        pol = _policy()
        mgr = CheckpointManager(ShardedStore(StoreConfig(str(tmp_path))),
                                pol)
        tree = small_tree()
        mgr.checkpoint(7, tree, block=True)
        # catastrophic store loss
        for g in mgr.store.generations():
            for p in sorted(g.glob("**/*"), reverse=True):
                p.unlink()
            g.rmdir()
        out, step, source = mgr.restore(tree)
        assert step == 7 and source == "buddy"
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(tree["a"]))

    def test_policy_cadence(self, tmp_path):
        pol = _policy(period=5.0)
        for _ in range(5):
            pol.observe_step_time(1.0)     # 1 s/step -> every 5 steps
        mgr = CheckpointManager(ShardedStore(StoreConfig(str(tmp_path))),
                                pol)
        tree = small_tree()
        saved = [step for step in range(1, 21)
                 if mgr.maybe_checkpoint(step, tree)]
        mgr.wait()
        assert saved == [1, 6, 11, 16]

    def test_measured_C_feeds_policy(self, tmp_path):
        pol = _policy(strategy="algo_t", C_s=99.0, mu_s=3600.0)
        mgr = CheckpointManager(ShardedStore(StoreConfig(str(tmp_path))),
                                pol)
        mgr.checkpoint(1, small_tree(), block=True)
        assert pol.checkpoint_params().C < 10.0   # measured, not the prior


# ---------------------------------------------------------------------------
# Manager multilevel paths (buddy every checkpoint, PFS every m-th)
# ---------------------------------------------------------------------------

class TestManagerMultilevel:
    def test_maybe_checkpoint_honors_pfs_every_m(self, tmp_path):
        """Every period ends in a buddy push; only every m-th goes deep."""
        pol = _policy(period=1.0)
        for _ in range(3):
            pol.observe_step_time(1.0)       # 1 s/step -> every step
        mgr = CheckpointManager(
            ShardedStore(StoreConfig(str(tmp_path))), pol,
            ManagerConfig(async_write=False, pfs_every=3))
        tree = small_tree()
        saved = [s for s in range(1, 10) if mgr.maybe_checkpoint(s, tree)]
        assert saved == list(range(1, 10))
        # deep writes at checkpoint ordinals 0, 3, 6 -> steps 1, 4, 7
        # (retention keeps the newest two PFS generations)
        gens = [g.name for g in mgr.store.generations()]
        assert gens == ["step_000000004", "step_000000007"]
        assert [s["level"] for s in mgr.stats] == [2, 1, 1] * 3
        # the buddy holds the freshest state -> newest-wins restore
        out, step, source = mgr.restore(tree)
        assert source == "buddy" and step == 9

    def test_buddy_restore_after_torn_pfs_write(self, tmp_path):
        """A torn deep write must not lose the fresher buddy state."""
        mgr = CheckpointManager(
            ShardedStore(StoreConfig(str(tmp_path))), _policy(),
            ManagerConfig(async_write=False, pfs_every=2))
        t1, t2 = small_tree(1), small_tree(2)
        mgr.checkpoint(1, t1)            # ordinal 0 -> deep (PFS + buddy)
        mgr.checkpoint(2, t2)            # ordinal 1 -> buddy only
        # tear the only PFS generation: shard corrupted post-commit
        gen = mgr.store.generations()[-1]
        shard = next(gen.glob("shard_*.npz"))
        data = bytearray(shard.read_bytes())
        data[50] ^= 0xFF
        shard.write_bytes(bytes(data))
        out, step, source = mgr.restore(t1)
        assert source == "buddy" and step == 2
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(t2["a"]))

    def test_compressed_roundtrip_through_recovery(self, tmp_path):
        """compress=True checkpoints survive the full manager recovery path
        (dequantization on restore, values within the int8 block bound)."""
        mgr = CheckpointManager(
            ShardedStore(StoreConfig(str(tmp_path), compress=True)),
            _policy(), ManagerConfig(async_write=False, use_buddy=False))
        tree = {"w": jax.random.normal(jax.random.key(3), (512, 512))}
        mgr.checkpoint(11, tree)
        out, step, source = mgr.restore(tree)
        assert step == 11 and source == "store"
        rel = float(jnp.max(jnp.abs(out["w"] - tree["w"]))
                    / jnp.max(jnp.abs(tree["w"])))
        assert rel < 0.01

    def test_pfs_every_without_buddy_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            CheckpointManager(
                ShardedStore(StoreConfig(str(tmp_path))), _policy(),
                ManagerConfig(use_buddy=False, pfs_every=2))

    def test_shallow_override_without_buddy_rejected(self, tmp_path):
        """deep=False with no buddy would persist nothing yet still count
        as a taken checkpoint — same invariant as the config guard."""
        mgr = CheckpointManager(
            ShardedStore(StoreConfig(str(tmp_path))), _policy(),
            ManagerConfig(async_write=False, use_buddy=False))
        with pytest.raises(ValueError):
            mgr.checkpoint(1, small_tree(), deep=False)
        assert mgr.stats == [] and mgr._last_ckpt_step is None


# ---------------------------------------------------------------------------
# Energy meter
# ---------------------------------------------------------------------------

class TestEnergyMeter:
    def test_phase_integration(self):
        m = EnergyMeter(PAPER_EXASCALE_PROFILE)
        m.add(Phase.COMPUTE, 10.0)
        m.add(Phase.CHECKPOINT_IO, 2.0)
        m.add(Phase.CHECKPOINT_IO, 1.0, advances_wall=False)  # overlapped
        m.add(Phase.DOWN, 1.0)
        e = m.energy_j()
        assert e["static"] == pytest.approx(13.0 * 10.0)
        assert e["compute"] == pytest.approx(10.0 * 10.0)
        assert e["io"] == pytest.approx(3.0 * 100.0)
        assert m.report()["rho"] == pytest.approx(5.5)

    def test_negative_interval_raises(self):
        m = EnergyMeter(PAPER_EXASCALE_PROFILE)
        with pytest.raises(ValueError):
            m.add(Phase.COMPUTE, -1.0)


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_flags_stragglers_and_escalates(self):
        w = StepTimeWatchdog()
        for i in range(20):
            assert not w.observe(i, 1.0 + 0.001 * (i % 3))
        assert w.observe(20, 5.0)
        assert w.observe(21, 5.0)
        assert w.observe(22, 5.0)
        assert w.events[-1]["escalate"]
        # baseline was not poisoned by the stragglers
        assert w.mean < 1.1

    def test_quiet_run_no_events(self):
        w = StepTimeWatchdog()
        rng = np.random.default_rng(0)
        for i in range(200):
            w.observe(i, 1.0 + 0.01 * rng.standard_normal())
        assert w.events == []

    def test_warmup_spike_absorbed_not_flagged(self):
        """Before min_samples the statistics are too green to trust: the
        spike is not flagged and it updates the baseline."""
        from repro.ft import WatchdogConfig
        w = StepTimeWatchdog(WatchdogConfig(min_samples=8))
        for i in range(3):
            w.observe(i, 1.0)
        assert not w.observe(3, 5.0)
        assert w.events == []
        assert w.mean > 1.0

    def test_escalation_resets_after_normal_step(self):
        from repro.ft import WatchdogConfig
        w = StepTimeWatchdog(WatchdogConfig(consecutive_to_escalate=3))
        for i in range(10):
            w.observe(i, 1.0)
        w.observe(10, 5.0)
        w.observe(11, 5.0)
        assert not w.events[-1]["escalate"]   # only 2 consecutive
        w.observe(12, 1.0)                    # recovery resets the streak
        w.observe(13, 5.0)
        assert not w.events[-1]["escalate"]

    def test_on_straggler_callback(self):
        seen = []
        w = StepTimeWatchdog(on_straggler=seen.append)
        for i in range(10):
            w.observe(i, 1.0)
        w.observe(10, 5.0)
        assert len(seen) == 1
        assert seen[0]["step"] == 10 and seen[0]["duration_s"] == 5.0


# ---------------------------------------------------------------------------
# Elastic plan
# ---------------------------------------------------------------------------

class TestElastic:
    def test_plan_shrinks_data_axis(self):
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(len(jax.devices()))
        plan = plan_reshard(mesh, n_failed_hosts=0, devices_per_host=1)
        assert plan.new_shape == dict(mesh.shape)

    def test_reshard_roundtrip_across_meshes(self, tmp_path):
        """Save under one mesh, restore under a smaller one."""
        store = ShardedStore(StoreConfig(str(tmp_path)))
        tree = small_tree()
        store.save(1, tree)
        out, _ = store.restore(tree)   # single-device 'new mesh'
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Fault-tolerant trainer end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_rig():
    cfg = reduced(get_config("starcoder2-3b"))
    m = build(cfg)
    ocfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1)
    step_fn = jax.jit(m.make_train_step(ocfg))
    return cfg, m, ocfg, step_fn


def _trainer(tmp, rig, mu_s, seed=0, steps=20, strategy="algo_t",
             process=None, pfs_every=1, q=0.0):
    cfg, m, ocfg, step_fn = rig
    params = m.init(jax.random.key(0))
    opt = adamw.init_state(params, ocfg)
    data = for_arch(cfg, batch=4, seq_len=64, seed=1)
    pol = CheckpointPolicy(PolicyConfig(strategy=strategy, C_s=0.05,
                                        R_s=0.05, D_s=0.1, mu_s=mu_s,
                                        omega=0.5), PW)
    mgr = CheckpointManager(ShardedStore(StoreConfig(root=str(tmp))), pol,
                            ManagerConfig(pfs_every=pfs_every))
    meter = EnergyMeter(PAPER_EXASCALE_PROFILE)
    inj = FailureInjector(FailureModel(mu_s=mu_s, downtime_s=0.1, seed=seed,
                                       process=process, buddy_loss_prob=q))
    return FaultTolerantTrainer(
        train_step=step_fn, state=(params, opt), data=data, policy=pol,
        manager=mgr, meter=meter, failures=inj,
        config=TrainerConfig(total_steps=steps, sim_seconds_per_step=1.0))


class TestFaultTolerantTrainer:
    def test_watchdog_wired_to_tracker_and_report(self, tmp_path, tiny_rig):
        """The trainer binds the watchdog's callback to its tracker and
        surfaces event counts in the report."""
        from repro.ft import MemoryTracker
        t = _trainer(tmp_path, tiny_rig, mu_s=float("inf"), steps=6)
        t.tracker = MemoryTracker()
        # warm the baseline, then push a straggler burst through the
        # trainer-bound callback (sim step time is constant, so the run
        # itself never flags)
        for i in range(10):
            t.watchdog.observe(i, 1.0)
        for i in range(3):
            t.watchdog.observe(10 + i, 6.0)
        rep = t.run()
        stragglers = t.tracker.of_kind("straggler")
        assert len(stragglers) == 3
        assert stragglers[-1]["escalate"]
        assert rep["straggler_events"] == 3
        assert rep["straggler_escalations"] == 1
        assert t.tracker.of_kind("step")      # step stream flows too

    def test_failures_do_not_change_result(self, tmp_path, tiny_rig):
        """Kill-anywhere property: final params identical with/without
        injected failures."""
        t_clean = _trainer(tmp_path / "clean", tiny_rig, mu_s=float("inf"))
        rep_c = t_clean.run()
        t_fail = _trainer(tmp_path / "fail", tiny_rig, mu_s=7.0, seed=3)
        rep_f = t_fail.run()
        assert rep_f["n_failures"] >= 1
        assert rep_f["final_step"] == rep_c["final_step"]
        for a, b in zip(jax.tree.leaves(t_clean.state[0]),
                        jax.tree.leaves(t_fail.state[0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("process_kw", [
        {"process": get_process("weibull", shape=0.7), "seed": 5},
        {"process": get_process("trace",
                                gaps=[5.0, 9.0, 4.0, 12.0, 6.0],
                                rescale=False), "seed": 0},
    ], ids=["weibull", "trace_replay"])
    def test_rollback_identity_any_process(self, tmp_path, tiny_rig,
                                           process_kw):
        """The kill-anywhere property must hold for every injector: the
        renewal-clock schedules (Weibull, trace replay) roll back through
        the same restore path as the legacy exponential."""
        t_clean = _trainer(tmp_path / "clean", tiny_rig, mu_s=float("inf"))
        rep_c = t_clean.run()
        t_fail = _trainer(tmp_path / "fail", tiny_rig, mu_s=7.0,
                          **process_kw)
        rep_f = t_fail.run()
        assert rep_f["n_failures"] >= 1
        assert rep_f["final_step"] == rep_c["final_step"]
        for a, b in zip(jax.tree.leaves(t_clean.state[0]),
                        jax.tree.leaves(t_fail.state[0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rollback_identity_multilevel(self, tmp_path, tiny_rig):
        """Kill-anywhere through the two-level manager: buddy-only
        checkpoints every period, PFS every 3rd, and hard failures
        (q=0.5) that drop the buddy and recover from the deep level."""
        t_clean = _trainer(tmp_path / "clean", tiny_rig, mu_s=float("inf"),
                           pfs_every=3)
        rep_c = t_clean.run()
        t_fail = _trainer(tmp_path / "fail", tiny_rig, mu_s=5.0, seed=2,
                          pfs_every=3, q=0.5)
        rep_f = t_fail.run()
        assert rep_f["n_failures"] >= 2
        # both checkpoint levels were exercised
        assert {c["level"] for c in t_fail.manager.stats} == {1, 2}
        assert rep_f["final_step"] == rep_c["final_step"]
        for a, b in zip(jax.tree.leaves(t_clean.state[0]),
                        jax.tree.leaves(t_fail.state[0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_hard_failure_recovers_from_store(self, tmp_path, tiny_rig):
        """q=1: every failure drops the buddy; recovery must come from the
        deep level and the run must still finish bit-identical."""
        t_clean = _trainer(tmp_path / "clean", tiny_rig, mu_s=float("inf"))
        rep_c = t_clean.run()
        t_fail = _trainer(tmp_path / "fail", tiny_rig, mu_s=8.0, seed=2,
                          q=1.0)
        rep_f = t_fail.run()
        assert rep_f["final_step"] == rep_c["final_step"]
        assert rep_f["n_failures"] >= 1
        assert rep_f["n_hard_failures"] == rep_f["n_failures"]
        sources = [e["source"] for e in t_fail.log
                   if e.get("event") == "rollback"]
        assert sources and all(s == "store" for s in sources)
        for a, b in zip(jax.tree.leaves(t_clean.state[0]),
                        jax.tree.leaves(t_fail.state[0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_loss_decreases(self, tmp_path, tiny_rig):
        t = _trainer(tmp_path, tiny_rig, mu_s=float("inf"), steps=10)
        rep = t.run()
        assert rep["losses"][-1] < rep["losses"][0]

    def test_failures_cost_time(self, tmp_path, tiny_rig):
        t_clean = _trainer(tmp_path / "c", tiny_rig, mu_s=float("inf"))
        t_fail = _trainer(tmp_path / "f", tiny_rig, mu_s=6.0, seed=1)
        rc, rf = t_clean.run(), t_fail.run()
        assert rf["wall_s"] > rc["wall_s"]
        assert rf["energy"]["E_total_j"] > rc["energy"]["E_total_j"]

    def test_energy_report_has_paper_parameters(self, tmp_path, tiny_rig):
        t = _trainer(tmp_path, tiny_rig, mu_s=50.0, steps=10)
        rep = t.run()
        assert rep["energy"]["rho"] == pytest.approx(5.5)
        assert "predicted_energy_ratio" in rep["policy"]

    def test_algo_e_longer_period_than_algo_t(self, tmp_path, tiny_rig):
        tt = _trainer(tmp_path / "t", tiny_rig, mu_s=200.0, strategy="algo_t")
        te = _trainer(tmp_path / "e", tiny_rig, mu_s=200.0, strategy="algo_e")
        assert te.policy.period_seconds() > tt.policy.period_seconds()
