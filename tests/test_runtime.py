"""Runtime: fault-tolerant training loop + tAPP-scheduled serving engine."""
import dataclasses

import jax
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import smoke_config
from repro.core.scheduler.topology import DistributionPolicy
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.steps import TrainState, make_train_step
from repro.models import Model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime.serve_engine import Replica, ServingEngine
from repro.runtime.train_loop import TrainLoopConfig, run_training

RNG = jax.random.PRNGKey(0)


def _training_setup(tmp_path, arch="smollm_135m", total=12):
    cfg = smoke_config(arch)
    model = Model(cfg)
    opt_cfg = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=total,
                          schedule="constant")
    params = model.init_params(RNG)
    state = TrainState(params=params, opt=adamw_init(opt_cfg, params))
    step_fn = jax.jit(make_train_step(cfg, opt_cfg))
    pipeline = SyntheticTokens(
        DataConfig(vocab_size=cfg.vocab_size, global_batch=4, seq_len=32)
    )
    ck = Checkpointer(str(tmp_path), keep_last=3)
    return cfg, state, step_fn, pipeline, ck


class TestTrainLoop:
    def test_loss_decreases(self, tmp_path):
        _, state, step_fn, pipeline, ck = _training_setup(tmp_path, total=25)
        report = run_training(
            step_fn=step_fn, state=state, pipeline=pipeline,
            checkpointer=ck,
            config=TrainLoopConfig(total_steps=25, checkpoint_every=10,
                                   checkpoint_async=False),
        )
        assert report.steps_run == 25
        first = np.mean(report.losses[:5])
        last = np.mean(report.losses[-5:])
        assert last < first, (first, last)

    def test_restart_after_injected_failure(self, tmp_path):
        _, state, step_fn, pipeline, ck = _training_setup(tmp_path, total=15)
        report = run_training(
            step_fn=step_fn, state=state, pipeline=pipeline, checkpointer=ck,
            config=TrainLoopConfig(
                total_steps=15, checkpoint_every=5, checkpoint_async=False,
                inject_failure_at=8,
            ),
        )
        assert report.restarts == 1
        assert report.final_step == 14
        assert ck.latest_step() == 14

    def test_resume_from_checkpoint(self, tmp_path):
        cfg, state, step_fn, pipeline, ck = _training_setup(tmp_path, total=10)
        run_training(
            step_fn=step_fn, state=state, pipeline=pipeline, checkpointer=ck,
            config=TrainLoopConfig(total_steps=6, checkpoint_every=5,
                                   checkpoint_async=False),
        )
        # Second invocation resumes from the saved step, not from scratch.
        report = run_training(
            step_fn=step_fn, state=state, pipeline=pipeline, checkpointer=ck,
            config=TrainLoopConfig(total_steps=10, checkpoint_every=5,
                                   checkpoint_async=False),
        )
        assert report.steps_run <= 5  # only the remaining steps ran


def _small_replica(name, zone, sets=(), slots=2, seed=0):
    cfg = dataclasses.replace(smoke_config("smollm_135m"), n_layers=2)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    return Replica(name, cfg, params, zone=zone, sets=sets, slots=slots,
                   max_len=48)


ZONED_SCRIPT = """
- default:
  - workers:
    - set:
    strategy: platform
    invalidate: overload
- edge_only:
  - controller: EdgeCtl
    workers:
    - set: edge
    topology_tolerance: none
  followup: fail
"""


class TestServingEngine:
    def test_completes_requests(self):
        engine = ServingEngine(tapp_script=ZONED_SCRIPT)
        engine.add_controller("EdgeCtl", zone="edge")
        engine.add_controller("CloudCtl", zone="cloud")
        engine.add_replica(_small_replica("r-edge", "edge", ["edge"]))
        engine.add_replica(_small_replica("r-cloud", "cloud", ["cloud"]))
        reqs = [
            engine.submit("smollm-135m", [1, 2, 3], max_new_tokens=4)
            for _ in range(5)
        ]
        engine.run_until_done(max_ticks=100)
        assert all(r.state == "done" for r in reqs)
        assert all(len(r.output) == 4 for r in reqs)

    def test_tagged_requests_pinned_to_zone(self):
        engine = ServingEngine(tapp_script=ZONED_SCRIPT)
        engine.add_controller("EdgeCtl", zone="edge")
        engine.add_controller("CloudCtl", zone="cloud")
        engine.add_replica(_small_replica("r-edge", "edge", ["edge"]))
        engine.add_replica(_small_replica("r-cloud", "cloud", ["cloud"]))
        reqs = [
            engine.submit("smollm-135m", [1, 2, 3], tag="edge_only",
                          max_new_tokens=3)
            for _ in range(4)
        ]
        engine.run_until_done(max_ticks=100)
        assert all(r.state == "done" for r in reqs)
        assert {r.replica for r in reqs} == {"r-edge"}

    def test_federated_engine_routes_by_entry_zone_and_forwards(self):
        """A federation-backed engine serves multi-entry traffic: requests
        enter their zone's gateway; edge-pinned work submitted at the
        cloud entry is forwarded to (and only to) the edge replica."""
        from repro.core.platform import (
            ClusterSpec,
            ControllerSpec,
            FederationSpec,
        )

        spec = FederationSpec.of({
            "edge": ClusterSpec(controllers=(ControllerSpec("EdgeCtl"),)),
            "cloud": ClusterSpec(controllers=(ControllerSpec("CloudCtl"),)),
        })
        engine = ServingEngine(tapp_script=ZONED_SCRIPT, federation=spec)
        engine.add_replica(_small_replica("r-edge", "edge", ["edge"]))
        engine.add_replica(_small_replica("r-cloud", "cloud", ["cloud"]))
        pinned = [
            engine.submit("smollm-135m", [1, 2, 3], tag="edge_only",
                          entry_zone="cloud", max_new_tokens=3)
            for _ in range(2)
        ]
        generic = engine.submit("smollm-135m", [4, 5], entry_zone="cloud",
                                max_new_tokens=3)
        engine.run_until_done(max_ticks=100)
        assert all(r.state == "done" for r in pinned + [generic])
        assert {r.replica for r in pinned} == {"r-edge"}
        assert generic.replica == "r-cloud"  # zone-local stays local
        stats = engine.platform.stats()
        assert stats.forwards >= 2
        assert stats.zone("edge").forwarded_in >= 2
        # The compat property resolves to the default entry's gateway.
        assert engine.gateway is engine.platform.zone_gateway("edge")

    def test_decode_is_deterministic_across_replicas(self):
        """Same weights on two replicas → same generation (placement-
        transparent serving)."""
        engine = ServingEngine(tapp_script=None)
        engine.add_controller("C", zone="z")
        r1 = _small_replica("r1", "z", seed=7)
        r2 = Replica("r2", r1.cfg, r1.params, zone="z", slots=2, max_len=48)
        engine.add_replica(r1)
        engine.add_replica(r2)
        a = engine.submit("smollm-135m", [5, 6, 7, 8], max_new_tokens=5)
        b = engine.submit("smollm-135m", [5, 6, 7, 8], max_new_tokens=5)
        engine.run_until_done(max_ticks=100)
        assert a.state == b.state == "done"
        assert a.output == b.output

    def test_failover_on_replica_loss(self):
        engine = ServingEngine(tapp_script=ZONED_SCRIPT)
        engine.add_controller("EdgeCtl", zone="edge")
        engine.add_controller("CloudCtl", zone="cloud")
        r_edge = _small_replica("r-edge", "edge", ["edge"], seed=1)
        engine.add_replica(r_edge)
        engine.add_replica(_small_replica("r-cloud", "cloud", ["cloud"], seed=1))
        reqs = [
            engine.submit("smollm-135m", [1, 2], max_new_tokens=6)
            for _ in range(3)
        ]
        engine.step_once()
        engine.remove_replica("r-edge")  # node failure mid-flight
        engine.run_until_done(max_ticks=200)
        assert all(r.state == "done" for r in reqs)
        assert all(r.replica == "r-cloud" for r in reqs)

    def test_edge_only_fails_when_zone_lost(self):
        engine = ServingEngine(tapp_script=ZONED_SCRIPT)
        engine.add_controller("EdgeCtl", zone="edge")
        engine.add_controller("CloudCtl", zone="cloud")
        engine.add_replica(_small_replica("r-cloud", "cloud", ["cloud"]))
        req = engine.submit("smollm-135m", [1, 2], tag="edge_only",
                            max_new_tokens=2)
        for _ in range(3):
            engine.step_once()
        assert req.state == "queued"  # policy refuses the cloud replica

    def test_capacity_spills_to_second_replica(self):
        engine = ServingEngine(
            tapp_script=None, distribution=DistributionPolicy.SHARED
        )
        engine.add_controller("C", zone="z")
        r1 = _small_replica("r1", "z", slots=1, seed=3)
        r2 = Replica("r2", r1.cfg, r1.params, zone="z", slots=1, max_len=48)
        engine.add_replica(r1)
        engine.add_replica(r2)
        reqs = [
            engine.submit("smollm-135m", [9, 9], max_new_tokens=6)
            for _ in range(2)
        ]
        engine.run_until_done(max_ticks=200)
        assert all(r.state == "done" for r in reqs)
        assert {r.replica for r in reqs} == {"r1", "r2"}


class TestDonatedDecode:
    def test_served_tokens_match_requests_served_alone(self):
        """Decode donates the slot cache and writes only the new token's
        K/V into it. Requests served together, through admission, decode
        steps, admissions merged into a cache that decode steps have since
        written, and a replica loss that requeues its requests, get the
        tokens each gets served alone; every step's old cache is dead
        after it, and none is read again."""
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14],
                   [15, 16, 17]]
        alone = ServingEngine(tapp_script=None)
        alone.add_controller("C", zone="z")
        alone.add_replica(_small_replica("r", "z", seed=1))
        want = []
        for prompt in prompts:
            req = alone.submit("smollm-135m", prompt, max_new_tokens=6)
            alone.run_until_done(max_ticks=50)
            want.append(req.output)

        engine = ServingEngine(tapp_script=ZONED_SCRIPT)
        engine.add_controller("EdgeCtl", zone="edge")
        engine.add_controller("CloudCtl", zone="cloud")
        replicas = [_small_replica("r-edge", "edge", ["edge"], seed=1),
                    _small_replica("r-cloud", "cloud", ["cloud"], seed=1)]
        for replica in replicas:
            engine.add_replica(replica)
        reqs = [engine.submit("smollm-135m", p, max_new_tokens=6)
                for p in prompts[:3]]
        engine.step_once()
        held = [leaf for r in replicas if r.active
                for leaf in jax.tree.leaves(r.cache)]
        engine.step_once()
        assert held and all(leaf.is_deleted() for leaf in held)
        # Two more arrive after decode steps: one is merged into a slot of
        # a cache the steps have written, one waits for a slot.
        reqs += [engine.submit("smollm-135m", p, max_new_tokens=6)
                 for p in prompts[3:]]
        engine.step_once()
        assert any(r.replica == "r-edge" and r.state == "running"
                   for r in reqs)
        engine.remove_replica("r-edge")
        engine.run_until_done(max_ticks=200)
        assert all(r.state == "done" for r in reqs)
        assert [r.output for r in reqs] == want


class TestRequestTimes:
    def test_each_time_is_set_when_its_state_changes(self):
        import time as _time

        engine = ServingEngine(tapp_script=None)
        engine.add_controller("C", zone="z")
        engine.add_replica(_small_replica("r", "z", slots=2, seed=3))
        before = _time.perf_counter()
        req = engine.submit("smollm-135m", [5, 6, 7], max_new_tokens=4)
        assert before <= req.submitted_s <= _time.perf_counter()
        assert req.admitted_s is req.first_token_s is req.finished_s is None
        engine.step_once()
        # Admitted and decoded once: two tokens, not yet finished.
        assert len(req.output) == 2 and req.finished_s is None
        assert req.submitted_s <= req.admitted_s <= req.first_token_s
        engine.run_until_done(max_ticks=20)
        assert req.state == "done" and req.first_token_s < req.finished_s


class TestStragglerMitigation:
    def test_replica_counts_decode_steps_and_times_the_last(self):
        import time as _time

        engine = ServingEngine(tapp_script=None)
        engine.add_controller("C", zone="z")
        replica = _small_replica("r", "z", slots=2, seed=7)
        engine.add_replica(replica)
        assert replica.step() == [] and replica.steps == 0
        req = engine.submit("smollm-135m", [5, 6], max_new_tokens=3)
        t0 = _time.perf_counter()
        engine.run_until_done(max_ticks=20)
        elapsed = _time.perf_counter() - t0
        assert req.state == "done"
        # The first token comes from admission; two decode steps follow.
        assert replica.steps == 2
        assert 0.0 < replica.last_step_s < elapsed

    def test_slow_replica_is_flagged_and_routed_around(self, monkeypatch):
        import time as _time

        engine = ServingEngine(tapp_script=None, straggler_factor=2.0)
        engine.add_controller("C", zone="z")
        fast = _small_replica("fast", "z", slots=4, seed=5)
        slow = Replica("slow", fast.cfg, fast.params, zone="z", slots=4,
                       max_len=48)
        engine.add_replica(fast)
        engine.add_replica(slow)

        # Warm both replicas so each EMA exists (both get load: 8 reqs on
        # 2 replicas x 4 slots).
        for _ in range(8):
            engine.submit("smollm-135m", [1, 2], max_new_tokens=3)
        engine.run_until_done(max_ticks=80)
        assert fast.steps >= 2 and slow.steps >= 2

        # Make 'slow' a straggler: its decode call stalls (timed region).
        orig_decode = slow._decode

        def slow_decode(*args, **kwargs):
            _time.sleep(0.25)
            return orig_decode(*args, **kwargs)

        monkeypatch.setattr(slow, "_decode", slow_decode)
        reqs = [engine.submit("smollm-135m", [3, 4], max_new_tokens=4)
                for _ in range(6)]
        engine.run_until_done(max_ticks=200)
        assert all(r.state == "done" for r in reqs)
        # The straggler was flagged at least once and reported saturated.
        assert engine.stragglers_flagged >= 1
