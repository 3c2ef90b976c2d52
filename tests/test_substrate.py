"""Data pipeline, checkpointing, and runtime fault-tolerance tests."""
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:     # property tests importorskip; the rest still run
    HAVE_HYPOTHESIS = False

from repro.ckpt.checkpoint import (CheckpointManager, latest_step,
                                   restore_checkpoint, save_checkpoint)
from repro.data.pipeline import DataPipeline, synthetic_batch
from repro.runtime.ft import (HeartbeatMonitor, StepWatchdog, StragglerStats)


# ----------------------------------------------------------------------
# data pipeline
# ----------------------------------------------------------------------
class TestSyntheticData:
    def test_deterministic_across_calls(self):
        a = synthetic_batch(1, 5, 0, 2, 8, 32, 1000)
        b = synthetic_batch(1, 5, 0, 2, 8, 32, 1000)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_steps_differ(self):
        a = synthetic_batch(1, 5, 0, 1, 8, 32, 1000)
        b = synthetic_batch(1, 6, 0, 1, 8, 32, 1000)
        assert not np.array_equal(a["tokens"], b["tokens"])

    def test_labels_are_next_token_shift(self):
        full = synthetic_batch(3, 0, 0, 1, 4, 64, 500)
        # label[t] must equal token[t+1] of the same underlying stream.
        assert full["labels"].shape == full["tokens"].shape
        np.testing.assert_array_equal(full["tokens"][:, 1:],
                                      full["labels"][:, :-1])

    if HAVE_HYPOTHESIS:
        @given(st.integers(1, 8).filter(lambda n: 16 % n == 0))
        @settings(max_examples=10, deadline=None)
        def test_shards_partition_global_batch(self, n_shards):
            full = synthetic_batch(9, 2, 0, 1, 16, 16, 100)
            parts = [synthetic_batch(9, 2, s, n_shards, 16, 16, 100)
                     for s in range(n_shards)]
            np.testing.assert_array_equal(
                np.concatenate([p["tokens"] for p in parts]), full["tokens"])
    else:
        def test_shards_partition_global_batch(self):
            pytest.importorskip("hypothesis")

    def test_tokens_in_vocab_range(self):
        b = synthetic_batch(0, 0, 0, 1, 8, 128, 313)
        assert b["tokens"].min() >= 0
        assert b["tokens"].max() < 313

    def test_marginal_is_skewed(self):
        """Low token ids should be more frequent (learnable signal)."""
        b = synthetic_batch(0, 0, 0, 1, 64, 256, 1000)
        low = (b["tokens"] < 250).mean()
        assert low > 0.4      # ~50% of mass in the lowest quartile


class TestPipeline:
    def test_prefetch_matches_synchronous(self):
        kw = dict(seed=4, global_batch=4, seq_len=16, vocab=100)
        sync = DataPipeline(**kw)
        pre = DataPipeline(**kw)
        pre.start()
        try:
            for _ in range(5):
                np.testing.assert_array_equal(next(sync)["tokens"],
                                              next(pre)["tokens"])
        finally:
            pre.stop()

    def test_restore_resumes_exact_stream(self):
        kw = dict(seed=4, global_batch=4, seq_len=16, vocab=100)
        p = DataPipeline(**kw)
        for _ in range(3):
            next(p)
        st_ = p.state()
        want = next(p)
        p2 = DataPipeline(**kw)
        p2.restore(st_)
        np.testing.assert_array_equal(next(p2)["tokens"], want["tokens"])

    def test_rebalance_preserves_coverage(self):
        kw = dict(seed=4, global_batch=8, seq_len=16, vocab=100)
        p = DataPipeline(**kw, shard=0, n_shards=2)
        next(p)
        p.rebalance(shard=1, n_shards=4)          # elastic resize
        got = next(p)
        want = synthetic_batch(4, 1, 1, 4, 8, 16, 100)
        np.testing.assert_array_equal(got["tokens"], want["tokens"])


# ----------------------------------------------------------------------
# checkpointing
# ----------------------------------------------------------------------
class TestCheckpoint:
    def tree(self):
        return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                "nested": {"b": jnp.ones((5,), jnp.bfloat16)},
                "step": jnp.int32(7)}

    def test_roundtrip_including_bf16(self, tmp_path):
        t = self.tree()
        save_checkpoint(tmp_path, 3, t)
        got = restore_checkpoint(tmp_path, t)
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_latest_step_ignores_uncommitted_tmp(self, tmp_path):
        save_checkpoint(tmp_path, 1, self.tree())
        (tmp_path / "step_00000002.tmp").mkdir()      # simulated crash
        assert latest_step(tmp_path) == 1

    def test_structure_mismatch_raises(self, tmp_path):
        save_checkpoint(tmp_path, 1, self.tree())
        with pytest.raises(ValueError):
            restore_checkpoint(tmp_path, {"only": jnp.zeros(3)})

    def test_async_manager_retention_and_gc(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        t = self.tree()
        for s in (10, 20, 30):
            mgr.save_async(s, t)
        mgr.wait()
        assert latest_step(tmp_path) == 30
        kept = sorted(d.name for d in tmp_path.iterdir())
        assert kept == ["step_00000020", "step_00000030"]

    def test_restore_latest_roundtrip(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        t = self.tree()
        mgr.save_async(5, t)
        mgr.wait()
        step, got = mgr.restore_latest(t)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(t["w"]))

    def test_elastic_restore_under_new_sharding(self, tmp_path):
        """Restore re-places leaves with explicit shardings (the region-
        reprogram path)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("data",))
        t = {"w": jnp.arange(8, dtype=jnp.float32)}
        save_checkpoint(tmp_path, 1, t)
        sh = {"w": NamedSharding(mesh, P("data"))}
        got = restore_checkpoint(tmp_path, t, shardings=sh)
        assert got["w"].sharding == sh["w"]


# ----------------------------------------------------------------------
# fault tolerance
# ----------------------------------------------------------------------
class TestWatchdog:
    def test_deadline_pass_and_fail(self):
        wd = StepWatchdog(deadline_s=10.0)
        wd.arm(0)
        assert wd.check() is True
        wd2 = StepWatchdog(deadline_s=0.0)
        wd2.arm(1)
        time.sleep(0.01)
        assert wd2.check() is False
        assert wd2.events[0].step == 1


class TestHeartbeat:
    def test_missed_heartbeat_demotes_via_erm(self):
        from repro.core.elastic import (ON_SERVER, ElasticResourceManager,
                                        Region)
        from repro.core.module import ModuleFootprint
        clock = [0.0]
        mon = HeartbeatMonitor([0, 1], timeout_s=5.0,
                               clock=lambda: clock[0])
        erm = ElasticResourceManager(
            [Region(rid=i, n_chips=8, hbm_bytes=1 << 34) for i in (0, 1)])
        erm.submit("a", [ModuleFootprint(1 << 30, 1e9, 4096)] * 2)

        clock[0] = 3.0
        mon.beat(0)                     # region 0 stays alive
        clock[0] = 6.0
        failed = mon.sweep(erm)
        assert failed == [1]
        assert erm.placement_of("a")[1] == ON_SERVER

        mon.heal(1, erm)
        assert erm.placement_of("a")[1] != ON_SERVER

    def test_beat_clears_failure(self):
        clock = [0.0]
        mon = HeartbeatMonitor([0], timeout_s=1.0, clock=lambda: clock[0])
        clock[0] = 2.0
        assert mon.sweep() == [0]
        mon.beat(0)
        assert 0 not in mon.failed


class TestStragglers:
    def test_persistent_outlier_flagged(self):
        stats = StragglerStats([0, 1, 2, 3], threshold=1.5, patience=3)
        flagged = []
        for _ in range(5):
            for r in (0, 1, 2):
                stats.record(r, 1.0)
            stats.record(3, 3.0)               # persistent straggler
            flagged = stats.stragglers()
        assert flagged == [3]

    def test_transient_blip_not_flagged(self):
        stats = StragglerStats([0, 1, 2, 3], threshold=1.5, patience=3)
        for i in range(6):
            for r in (0, 1, 2):
                stats.record(r, 1.0)
            stats.record(3, 3.0 if i == 0 else 1.0)
            flagged = stats.stragglers()
        assert flagged == []


# ----------------------------------------------------------------------
# train loop end-to-end (tiny)
# ----------------------------------------------------------------------
class TestTrainLoop:
    def test_loss_decreases_and_resume_is_exact(self, tmp_path):
        from repro.configs import get_config
        from repro.runtime.train import TrainLoop, TrainLoopConfig

        cfg = get_config("tinyllama_1_1b", smoke=True)
        run = TrainLoopConfig(steps=40, global_batch=8, seq_len=64,
                              ckpt_every=20, log_every=5, lr=3e-3,
                              warmup=5, seed=1)
        loop = TrainLoop(cfg, run, ckpt_dir=tmp_path)
        hist = loop.run_loop()
        losses = [h["loss"] for h in hist]
        assert all(np.isfinite(losses))
        assert min(losses[-3:]) < losses[0], "loss did not decrease"

        # Crash-restart: resumes at the last committed step.
        loop2 = TrainLoop(cfg, run, ckpt_dir=tmp_path, resume=True)
        assert loop2.start_step == 40
        assert loop2.pipeline.state().step == 40


# ----------------------------------------------------------------------
# roofline peak table
# ----------------------------------------------------------------------
class TestRooflinePeaks:
    def test_terms_use_the_target_chips_published_peaks(self):
        from repro.launch.roofline import RooflineTerms, chip_peaks

        v5e = chip_peaks("TPU v5 lite")
        assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
        t = RooflineTerms(arch="a", shape="s", mesh="pod", chips=1,
                          flops_per_device=197e12, bytes_per_device=819e9,
                          collective_bytes_per_device=0.0, collectives={})
        assert t.t_compute == t.t_memory == 1.0

    def test_unknown_device_kind_is_an_error(self):
        from repro.launch.roofline import RooflineTerms, chip_peaks

        with pytest.raises(ValueError, match="no published peaks"):
            chip_peaks("cpu")
        t = RooflineTerms(arch="a", shape="s", mesh="pod", chips=1,
                          flops_per_device=1.0, bytes_per_device=1.0,
                          collective_bytes_per_device=0.0, collectives={},
                          device_kind="TPU v9")
        with pytest.raises(ValueError):
            t.roofline_s
