"""tAPP-scheduled serving engine (continuous batching over model replicas).

The data-plane realisation of the paper's control plane:

  * a **replica** = one model hosted on a device group (a mesh slice on a
    TPU fleet; the host CPU in tests), with a fixed number of sequence
    *slots* and a slot-batched KV cache — the tAPP *worker*;
  * the **gateway** routes each request by its policy tag through the
    tAPP engine against live replica state (slots in use → capacity_used,
    health → overload, residency via worker-set labels = data locality);
  * **continuous batching**: prefill admits a sequence into a free slot;
    every engine tick runs ONE batched decode step per replica across all
    active slots (fixed batch shape → no recompilation);
  * **straggler mitigation**: decode-step-time EMA per replica; slow replicas
    are reported to the watcher with saturated capacity so tAPP policies
    route around them until they recover (the paper's ``invalidate``
    machinery doing data-plane duty);
  * **failure handling**: a dead replica is marked unreachable; its
    queued work is rescheduled by the same policy evaluation.

While a JAX profiler session runs, the engine loop, admission, each decode
step and each device-to-host read are spans of the session's trace
(``repro.runtime.spans``); each ``Request`` carries its own times.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.platform import (
    FederationSpec,
    TappFederation,
    TappPlatform,
    WorkerSpec,
)
from repro.core.scheduler.controller import ControllerRuntime
from repro.core.scheduler.engine import Invocation
from repro.core.scheduler.gateway import Gateway
from repro.core.scheduler.topology import DistributionPolicy
from repro.core.scheduler.watcher import Watcher
from repro.models.api import Model
from repro.models.config import ModelConfig
from repro.runtime.spans import span


@dataclasses.dataclass
class Request:
    request_id: int
    model_id: str
    tokens: np.ndarray                  # prompt [S]
    max_new_tokens: int = 8
    tag: Optional[str] = None
    # Federation entry zone (None: the single gateway / default entry).
    entry_zone: Optional[str] = None
    # lifecycle
    state: str = "queued"               # queued | running | done | failed
    output: List[int] = dataclasses.field(default_factory=list)
    replica: Optional[str] = None
    error: Optional[str] = None
    submitted_tick: int = 0
    finished_tick: int = 0
    # ``time.perf_counter()`` seconds at each change of state.
    submitted_s: float = dataclasses.field(default_factory=time.perf_counter)
    admitted_s: Optional[float] = None      # admission into a slot began
    first_token_s: Optional[float] = None   # first token read back
    finished_s: Optional[float] = None      # last token read back


@dataclasses.dataclass
class _SlotState:
    request: Request
    position: int                       # next cache slot to write
    last_token: int
    placement: object                   # the platform Placement ticket


def jit_decode(model: Model):
    """The replica's decode program. The slot cache (argument 1) is
    donated: the step's cache is the buffer it was given, with the new
    token's K/V written into it in place, and the caller's old reference
    to it is dead."""
    return jax.jit(model.decode, donate_argnums=(1,))


class Replica:
    """One model replica with slot-batched caches.

    Its params and slot cache live on ``device`` (the first device when
    None), so prefill, the slot merge and decode all run there.
    """

    def __init__(
        self,
        name: str,
        cfg: ModelConfig,
        params,
        *,
        zone: str = "default",
        sets: Sequence[str] = (),
        slots: int = 4,
        max_len: int = 128,
        device: Optional[jax.Device] = None,
    ) -> None:
        self.name = name
        self.cfg = cfg
        self.model = Model(cfg)
        self.device = device if device is not None else jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.zone = zone
        self.sets = frozenset(set(sets) | {cfg.name, "any"})
        self.slots = slots
        self.max_len = max_len
        self.cache = jax.device_put(
            self.model.init_cache(slots, max_len, enc_len=max_len), self.device
        )
        self.active: Dict[int, _SlotState] = {}   # slot index -> state
        self.alive = True
        self._decode = jit_decode(self.model)

        def prefill_b1(params, batch):
            cache = self.model.init_cache(1, max_len, enc_len=max_len)
            return self.model.prefill(params, batch, cache)

        self._prefill_b1 = jax.jit(prefill_b1)
        # Host seconds of the last decode step, and decode steps taken.
        self.last_step_s = 0.0
        self.steps = 0

    # -- slot management -----------------------------------------------------------

    def free_slot(self) -> Optional[int]:
        for i in range(self.slots):
            if i not in self.active:
                return i
        return None

    def prefill(self, slot: int, tokens: np.ndarray) -> jax.Array:
        """Prefill ``tokens`` into ``slot``; returns the last position's
        logits ``[V]``."""
        prompt = jax.device_put(np.asarray(tokens, np.int32)[None, :], self.device)
        batch = {"tokens": prompt}
        if self.cfg.family == "encdec":
            batch["frames"] = jax.device_put(
                np.zeros((1, prompt.shape[1], self.cfg.d_model), np.float32),
                self.device,
            )
        logits, filled = self._prefill_b1(self.params, batch)
        # Merge the single-sequence cache into this replica's slot.
        self.cache = jax.tree.map(
            lambda big, one: big.at[:, slot].set(one[:, 0]), self.cache, filled
        )
        return logits[0, -1]

    def decode(self, tokens: np.ndarray, positions: np.ndarray) -> jax.Array:
        """One batched decode step over every slot; returns logits
        ``[slots, V]``."""
        logits, self.cache = self._decode(
            self.params, self.cache,
            jax.device_put(tokens, self.device),
            jax.device_put(positions, self.device),
        )
        return logits[:, 0, :]

    def admit(self, request: Request, placement) -> bool:
        slot = self.free_slot()
        if slot is None or not self.alive:
            return False
        request.admitted_s = time.perf_counter()
        with span("serve.admit", request=request.request_id, device=self.device.id):
            first = jnp.argmax(self.prefill(slot, request.tokens))
            with span("serve.sync", kind="admit", request=request.request_id):
                first_token = int(first)
            request.first_token_s = time.perf_counter()
            self.active[slot] = _SlotState(
                request=request,
                position=len(request.tokens),
                last_token=first_token,
                placement=placement,
            )
            request.state = "running"
            request.replica = self.name
            request.output.append(first_token)
        return True

    # -- decode tick --------------------------------------------------------------------

    def step(self) -> List[Tuple[Request, object]]:
        """One batched decode step; returns finished (request, placement)."""
        if not self.active or not self.alive:
            return []
        t0 = time.perf_counter()
        with span("serve.step", device=self.device.id):
            tokens = np.zeros((self.slots,), np.int32)
            positions = np.zeros((self.slots,), np.int32)
            for slot, st in self.active.items():
                tokens[slot] = st.last_token
                positions[slot] = st.position
            next_ids = jnp.argmax(self.decode(tokens, positions), axis=-1)
            with span("serve.sync", kind="decode"):
                next_tokens = np.asarray(next_ids)
            now = time.perf_counter()
            finished: List[Tuple[Request, object]] = []
            for slot in list(self.active):
                st = self.active[slot]
                st.position += 1
                st.last_token = int(next_tokens[slot])
                st.request.output.append(st.last_token)
                done = (
                    len(st.request.output) >= st.request.max_new_tokens
                    or st.position >= self.max_len - 1
                )
                if done:
                    st.request.state = "done"
                    st.request.finished_s = now
                    finished.append((st.request, st.placement))
                    del self.active[slot]
        self.last_step_s = time.perf_counter() - t0
        self.steps += 1
        return finished

    def fail(self) -> None:
        """Simulate a replica loss (host/ICI failure)."""
        self.alive = False

    @property
    def load_fraction(self) -> float:
        return len(self.active) / max(1, self.slots)


class ServingEngine:
    def __init__(
        self,
        *,
        distribution: DistributionPolicy = DistributionPolicy.SHARED,
        tapp_script: Optional[str] = None,
        straggler_factor: float = 4.0,
        seed: int = 0,
        federation: Optional[FederationSpec] = None,
    ) -> None:
        # A federation spec turns the engine multi-entry: one ZoneGateway
        # per declared zone, requests routed from their submit()-time
        # entry zone and forwarded per the policy's topology_tolerance.
        # Replicas/controllers still register dynamically (the spec's
        # slices may be empty — they declare the zones).
        if federation is not None:
            self.platform: "TappPlatform | TappFederation" = TappFederation(
                federation, distribution=distribution, seed=seed
            )
        else:
            self.platform = TappPlatform(distribution=distribution, seed=seed)
        self.replicas: Dict[str, Replica] = {}
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self._ids = itertools.count()
        self.tick = 0
        self.straggler_factor = straggler_factor
        self._ema: Dict[str, float] = {}
        self.stragglers_flagged = 0
        if tapp_script is not None:
            self.platform.apply_policy(tapp_script)

    # -- platform access (compat: the engine predates the façade) -------------------

    @property
    def watcher(self) -> Watcher:
        return self.platform.watcher

    @property
    def gateway(self) -> Gateway:
        """The single entrypoint — or, on a federation-backed engine, the
        default entry zone's gateway (keeps the compat surface working:
        stats, probes, prewarm all behave per-zone there)."""
        if isinstance(self.platform, TappFederation):
            return self.platform.zone_gateway(self.platform.spec.entry_zone)
        return self.platform.gateway

    @property
    def runtime(self) -> ControllerRuntime:
        return self.platform.runtime

    # -- topology -------------------------------------------------------------------

    def add_controller(self, name: str, zone: str = "default") -> None:
        self.platform.add_controller(name, zone=zone)

    def add_replica(self, replica: Replica) -> None:
        self.replicas[replica.name] = replica
        self.platform.add_worker(
            WorkerSpec(
                name=replica.name,
                zone=replica.zone,
                sets=tuple(replica.sets),
                capacity_slots=replica.slots,
                resident_models=(replica.cfg.name,),
            )
        )

    def remove_replica(self, name: str) -> None:
        """Elastic scale-down / failure eviction."""
        replica = self.replicas.get(name)
        if replica is not None:
            replica.fail()
            for st in list(replica.active.values()):
                # Retire the ticket of the lost placement; the requeued
                # request gets a fresh one when it is re-admitted.
                st.placement.complete()
                st.request.state = "queued"
                st.request.replica = None
                st.request.output.clear()
                self.queue.append(st.request)
            replica.active.clear()
        self.platform.remove_worker(name)

    # -- requests ------------------------------------------------------------------------

    def submit(
        self,
        model_id: str,
        tokens: Sequence[int],
        *,
        tag: Optional[str] = None,
        max_new_tokens: int = 8,
        entry_zone: Optional[str] = None,
    ) -> Request:
        if entry_zone is not None and not isinstance(
            self.platform, TappFederation
        ):
            raise ValueError(
                f"entry_zone={entry_zone!r} requires a federation-backed "
                f"engine (pass federation=FederationSpec.of(...))"
            )
        req = Request(
            request_id=next(self._ids),
            model_id=model_id,
            tokens=np.asarray(tokens, np.int32),
            max_new_tokens=max_new_tokens,
            tag=tag,
            entry_zone=entry_zone,
            submitted_tick=self.tick,
        )
        self.queue.append(req)
        return req

    # -- engine loop ----------------------------------------------------------------------

    def step_once(self) -> None:
        with span("serve.tick"):
            self.tick += 1
            self._heartbeats()
            self._admit_queued()
            for replica in self.replicas.values():
                finished = replica.step()
                for request, placement in finished:
                    request.finished_tick = self.tick
                    placement.complete()
                    self.done.append(request)
            self._flag_stragglers()

    def run_until_done(self, max_ticks: int = 1000) -> None:
        for _ in range(max_ticks):
            if not self.queue and not any(
                r.active for r in self.replicas.values()
            ):
                return
            self.step_once()

    # -- internals ---------------------------------------------------------------------------

    def _heartbeats(self) -> None:
        workers = self.platform.cluster.workers
        for replica in self.replicas.values():
            if replica.name not in workers:
                continue
            self.platform.heartbeat(
                replica.name,
                healthy=replica.alive,
                reachable=replica.alive,
                capacity_used_pct=100.0 * replica.load_fraction,
            )

    def _admit_queued(self) -> None:
        if not self.queue:
            return
        still_queued: List[Request] = []
        requests = list(self.queue)
        invocations = [
            Invocation(
                function=request.model_id,
                tag=request.tag,
                model_id=request.model_id,
                request_id=request.request_id,
            )
            for request in requests
        ]
        pending = iter(requests)

        def _place(placement) -> None:
            request = next(pending)
            placed = False
            if placement.scheduled and placement.worker in self.replicas:
                replica = self.replicas[placement.worker]
                if replica.cfg.name == request.model_id:
                    placed = replica.admit(request, placement)
            if not placed:
                # Retire the unused ticket (no-op when never admitted) so
                # the running-function multiset stays truthful.
                placement.complete()
                request.state = "queued"
                still_queued.append(request)
                # Requests failed by policy (followup: fail) surface as such.
                if placement.failed_by_policy:
                    request.error = "policy-failed"

        # One unified invoke→admit pass per tick: the script version check,
        # plan compilation, and epoch-cached views are shared across the
        # queue, and each placement's admission lands before the next
        # decision is made (so capacity and affinity effects are observed,
        # exactly as the previous request-at-a-time loop did). On a
        # federation, each request enters at its submit()-time zone.
        if isinstance(self.platform, TappFederation):
            self.platform.invoke_batch(
                invocations,
                entry_zones=[request.entry_zone for request in requests],
                on_placement=_place,
            )
        else:
            self.platform.invoke_batch(invocations, on_placement=_place)
        self.queue = still_queued

    def _flag_stragglers(self) -> None:
        for replica in self.replicas.values():
            # Skip the first step: it includes jit compilation, which would
            # poison the EMA baseline (warmup exclusion).
            if replica.steps < 2:
                continue
            dt = replica.last_step_s
            ema = self._ema.get(replica.name)
            if ema is not None and dt > self.straggler_factor * ema:
                self.stragglers_flagged += 1
                # Route-around: report the replica as saturated until the
                # next healthy heartbeat shows recovered load.
                self.platform.heartbeat(
                    replica.name, capacity_used_pct=100.0
                )
            self._ema[replica.name] = (
                dt if ema is None else 0.9 * ema + 0.1 * dt
            )
