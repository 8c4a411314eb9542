"""Client-side request routing (egress).

`Client` maintains a live instance list for an endpoint (static list or a
discovery-store watch — reference: lib/runtime/src/component/client.rs:1-224).
`PushRouter` picks an instance per request — Random / RoundRobin / Direct /
KV-aware — and dispatches to it one of two ways, decided by what it finds,
not by a setting:

- **the wire** (any instance): publish the request envelope to the
  instance's bus subject with embedded TCP connection info, wait for the
  worker's connect-back, and yield the msgpack frames of the response stream
  (reference: lib/runtime/src/pipeline/network/egress/push_router.rs:65-203,
  addressed_router.rs:59-178);
- **the local call** (an instance this very runtime serves and offers,
  ``DistributedRuntime.local_instances``): the engine's own async generator
  through ``ServedInstance.local_stream`` — no envelope, publish,
  connect-back, socket or handler task (reference: `dynamo-run in=http
  out=<engine>` hands the local engine to the HTTP service in-process; only
  `out=dyn` goes over the network).

Picking, the ``fleet.worker_kill`` fault point, mark-dead, the ``route`` span,
``worker_id`` and the per-frame trace touch are one code path for both;
``router_dispatch_local_total`` / ``router_dispatch_wire_total`` (FAILOVER)
count which was taken.
"""

from __future__ import annotations

import asyncio
import enum
import logging
import random
import uuid
from typing import Any, AsyncIterator

import msgpack

from dynamo_tpu.runtime.component import EndpointId, Instance
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.failover import FAILOVER
from dynamo_tpu.runtime.transports.store import EventKind
from dynamo_tpu.utils.faults import FAULTS
from dynamo_tpu.utils.task import spawn_tracked
from dynamo_tpu.utils.tracing import tracer

logger = logging.getLogger(__name__)

#: How long a dispatched worker gets to open its response connection
#: before the dispatch counts as dead (the reverse-connection analogue
#: of connection-refused). The connect-back happens BEFORE any engine
#: work, so this bounds only the handshake, never prefill.
DEFAULT_CONNECT_TIMEOUT_S = 5.0

#: Distinct instances one generate() call will try before giving up on
#: dispatch (each failure marks that instance dead first).
MAX_DISPATCH_ATTEMPTS = 8


class RouterMode(enum.Enum):
    RANDOM = "random"
    ROUND_ROBIN = "round_robin"
    DIRECT = "direct"
    KV = "kv"


class Client:
    """Instance source for one endpoint, kept live via a store watch."""

    def __init__(self, drt, endpoint_id: EndpointId) -> None:
        self._drt = drt
        self.endpoint_id = endpoint_id
        self._instances: dict[int, Instance] = {}
        self._watch_task: asyncio.Task | None = None
        self._event = asyncio.Event()
        # Evictions since the last store re-resolve: a FALSELY
        # marked-dead worker (transient blip, missed connect-back) has
        # no watch event to bring it back — lease keepalive touches the
        # TTL, not the key — so the next pick after an eviction goes
        # back to the store once, instead of leaking that worker from
        # this process's view until it re-registers.
        self._evicted_since_refresh = False
        self._refreshing = False
        # Watch-DELETE tombstones (id -> monotonic stamp): a refresh's
        # store snapshot is read BEFORE the await completes, so a worker
        # that deregistered mid-refresh would be resurrected from the
        # stale bytes — and no further watch event would ever remove it.
        # Deletes stamped after the snapshot started win over it.
        self._deleted: dict[int, float] = {}

    @staticmethod
    async def create(drt, endpoint_id: EndpointId) -> "Client":
        client = Client(drt, endpoint_id)
        watch = await drt.store.watch_prefix(endpoint_id.etcd_prefix)
        for _, raw in watch.initial.items():
            inst = Instance.from_json(raw)
            client._instances[inst.instance_id] = inst
        client._event.set() if client._instances else None
        client._watch_task = asyncio.ensure_future(client._pump(watch))
        drt.runtime.token.on_cancel(watch.cancel)
        return client

    async def _pump(self, watch) -> None:
        async for ev in watch:
            if ev.kind is EventKind.PUT and ev.value:
                inst = Instance.from_json(ev.value)
                self._instances[inst.instance_id] = inst
                self._deleted.pop(inst.instance_id, None)
                self._event.set()
            elif ev.kind is EventKind.DELETE:
                lease_hex = ev.key.rsplit(":", 1)[-1]
                try:
                    wid = int(lease_hex, 16)
                except ValueError:
                    continue
                self._instances.pop(wid, None)
                self._deleted[wid] = asyncio.get_running_loop().time()

    def instances(self) -> list[Instance]:
        return list(self._instances.values())

    def instance_ids(self) -> list[int]:
        return list(self._instances.keys())

    def evict(self, instance_id: int) -> bool:
        """Immediate removal from the live view (the mark-dead fast
        path): a dispatch that hit a corpse must not wait out the lease
        TTL before the next request stops routing to it. The discovery
        store is untouched — lease expiry (or an explicit deregister)
        remains the authoritative cleanup."""
        self._evicted_since_refresh = True
        return self._instances.pop(instance_id, None) is not None

    async def refresh(self) -> list[Instance]:
        """Re-read the authoritative instance set from the discovery
        store. The recovery path for a FALSE mark-dead (a router-side
        network blip poisons the whole local view): watch events only
        fire on store changes, so an evicted-but-alive worker would
        otherwise never come back until it re-registered."""
        t0 = asyncio.get_running_loop().time()
        # Re-arm BEFORE the snapshot read: an eviction landing while the
        # store call is in flight must trigger the NEXT background
        # revalidate — clearing the flag after the await would discard
        # exactly that signal (and this refresh's stale snapshot is what
        # resurrects the concurrently-evicted corpse).
        self._evicted_since_refresh = False
        raw = await self._drt.store.get_prefix(self.endpoint_id.etcd_prefix)
        fresh: dict[int, Instance] = {}
        for value in raw.values():
            try:
                inst = Instance.from_json(value)
            except Exception:  # noqa: BLE001 — skip torn entries
                logger.warning("skipping malformed instance entry")
                continue
            # A DELETE that landed while the snapshot was in flight wins
            # over the snapshot's (necessarily older) bytes: a worker
            # that deregistered mid-refresh must not be resurrected into
            # the live view with no future event to remove it.
            if self._deleted.get(inst.instance_id, -1.0) >= t0:
                continue
            fresh[inst.instance_id] = inst
        self._instances = fresh
        # Tombstones only matter across one in-flight snapshot — prune
        # anything old so the map can't grow with fleet churn.
        for wid in [w for w, ts in self._deleted.items() if ts < t0]:
            del self._deleted[wid]
        if fresh:
            self._event.set()
        return list(fresh.values())

    async def _refresh_background(self) -> None:
        """Single-flight, non-blocking re-resolve after an eviction —
        the hot pick path never pays a store round trip; a falsely
        evicted worker reappears within one refresh instead of never."""
        if self._refreshing:
            return
        self._refreshing = True
        try:
            await self.refresh()
        except Exception:  # noqa: BLE001 — store blip: next eviction retries
            logger.debug("background instance refresh failed", exc_info=True)
        finally:
            self._refreshing = False

    async def wait_for_instances(self, timeout_s: float = 5.0) -> list[Instance]:
        if not self._instances:
            # The local view may be empty because mark-dead evicted
            # everything — re-resolve from the store before concluding
            # the endpoint has no capacity.
            try:
                await self.refresh()
            except Exception:  # noqa: BLE001 — store blip: fall through to wait
                logger.debug("instance refresh failed", exc_info=True)
        elif self._evicted_since_refresh:
            # Non-empty view with pending evictions: re-validate against
            # the store off the hot path (a TRUE corpse gets re-evicted
            # on its next failed dispatch; a false one comes back).
            spawn_tracked(
                self._refresh_background(), name="client-refresh"
            )
        if not self._instances:
            self._event.clear()
            await asyncio.wait_for(self._event.wait(), timeout_s)
        return self.instances()


class PushRouter:
    """Routes requests to instances; itself an AsyncEngine.

    KV-aware mode delegates instance choice to a `selector` callable
    (installed by the KV router layer) receiving the request payload and the
    live instance list.
    """

    def __init__(
        self,
        drt,
        client: Client,
        mode: RouterMode = RouterMode.ROUND_ROBIN,
        selector=None,
        connect_timeout_s: float = DEFAULT_CONNECT_TIMEOUT_S,
    ) -> None:
        self._drt = drt
        self.client = client
        self.mode = mode
        self.connect_timeout_s = connect_timeout_s
        self._selector = selector
        # Dead-worker hooks, fired with the instance id on every
        # mark_dead. A KV-aware selector's owning router is auto-wired:
        # the metrics aggregator drops the corpse's load snapshot and
        # the radix index prunes its blocks IN THE SAME STEP as the
        # routing eviction (satellite: ghosts used to linger until
        # endpoint_ttl_s).
        self.on_dead: list = []
        owner = getattr(selector, "__self__", None)
        hook = getattr(owner, "note_worker_dead", None)
        if hook is not None:
            self.on_dead.append(hook)
        # Whether the selector takes the request id (KvRouter.selector_fn
        # does — it binds the route-audit record to the request's trace);
        # legacy two-arg selectors keep working unchanged. Sniffed once,
        # not per request, and never via a TypeError probe (which would
        # mask a TypeError raised INSIDE the selector body).
        self._selector_takes_rid = False
        if selector is not None:
            import inspect

            try:
                params = inspect.signature(selector).parameters.values()
                self._selector_takes_rid = any(
                    p.name == "request_id" or p.kind is p.VAR_KEYWORD
                    for p in params
                )
            except (TypeError, ValueError):
                pass
        self._rr = 0

    @staticmethod
    async def create(
        drt, endpoint_id: EndpointId | str, mode: RouterMode = RouterMode.ROUND_ROBIN,
        selector=None,
        connect_timeout_s: float = DEFAULT_CONNECT_TIMEOUT_S,
    ) -> "PushRouter":
        if isinstance(endpoint_id, str):
            endpoint_id = EndpointId.parse(endpoint_id)
        client = await Client.create(drt, endpoint_id)
        return PushRouter(
            drt, client, mode, selector, connect_timeout_s=connect_timeout_s
        )

    async def _pick(
        self, payload: Any, instance_id: int | None,
        request_id: str | None = None,
        exclude: set[int] | None = None,
    ) -> Instance:
        try:
            instances = await self.client.wait_for_instances()
        except asyncio.TimeoutError:
            # Every instance evicted (rolling restart, drain, lease
            # expiry): a typed retryable rejection — the HTTP layer maps
            # it to 503 + Retry-After so clients back off and retry,
            # instead of a generic 500.
            from dynamo_tpu.llm.protocols.common import ShedError

            raise ShedError(
                f"no live instances for {self.client.endpoint_id}",
                retry_after_s=2.0,
            ) from None
        if exclude:
            # Failover re-dispatch: instances this request already found
            # dead stay out even if a store refresh re-added the corpse.
            instances = [
                i for i in instances if i.instance_id not in exclude
            ]
            if not instances:
                from dynamo_tpu.llm.protocols.common import ShedError

                raise ShedError(
                    f"every live instance of {self.client.endpoint_id} "
                    f"already failed this request",
                    retry_after_s=2.0,
                )
        if instance_id is not None:
            for inst in instances:
                if inst.instance_id == instance_id:
                    return inst
            raise LookupError(
                f"instance {instance_id:#x} not found for {self.client.endpoint_id}"
            )
        if self.mode is RouterMode.RANDOM:
            return random.choice(instances)
        if self.mode is RouterMode.ROUND_ROBIN:
            inst = instances[self._rr % len(instances)]
            self._rr += 1
            return inst
        if self.mode is RouterMode.KV:
            if self._selector is None:
                raise RuntimeError("KV mode requires a selector")
            chosen_id = await (
                self._selector(payload, instances, request_id=request_id)
                if self._selector_takes_rid
                else self._selector(payload, instances)
            )
            if exclude and chosen_id in exclude:
                # Stale selector metrics can still point at the corpse —
                # fall back to spreading over the surviving candidates.
                chosen_id = random.choice(instances).instance_id
            try:
                return await self._pick(payload, chosen_id, exclude=exclude)
            except LookupError:
                # The selector's choice raced a concurrent mark-dead
                # eviction (another request's failover removed it while
                # we awaited the selector). A healthy request must not
                # 500 on that race — spread over the survivors; if the
                # pick is ALSO a corpse, dispatch marks it dead and the
                # caller's retry loop moves on.
                survivors = [
                    i for i in instances if i.instance_id != chosen_id
                ]
                if not survivors:
                    from dynamo_tpu.llm.protocols.common import ShedError

                    raise ShedError(
                        f"no surviving instances for "
                        f"{self.client.endpoint_id}",
                        retry_after_s=2.0,
                    ) from None
                return random.choice(survivors)
        raise RuntimeError(f"direct mode requires instance_id")

    def mark_dead(self, instance_id: int, reason: str) -> None:
        """The mark-dead fast path: a typed transport failure against a
        worker immediately evicts it from the live routing view AND
        fires the on_dead hooks (metrics-aggregator poison + radix
        prune, plus the ``worker_dead`` broadcast that propagates the
        eviction to sibling router replicas — kv_router/router.py
        note_worker_dead) — in ONE step, instead of letting the ghost
        linger until the lease TTL / endpoint_ttl_s expire it. The same
        path covers dead ROUTER REPLICAS when the instances ARE
        replicas (a frontend spreading over N RouterServices —
        docs/architecture/ingress_scale.md): replica death and worker
        death are one taxonomy at this seam."""
        if self.client.evict(instance_id):
            FAILOVER.note_marked_dead(reason)
            logger.warning(
                "marked worker %#x dead (%s) — evicted from the live "
                "instance view", instance_id, reason,
            )
        for hook in self.on_dead:
            try:
                hook(instance_id)
            except Exception:  # noqa: BLE001 — a hook bug must not break routing
                logger.exception("on_dead hook failed for %#x", instance_id)

    def generate(
        self, request: Context, instance_id: int | None = None
    ) -> AsyncIterator[Any]:
        """The picked instance's stream. A dispatch that finds its worker
        dead marks it and, with nothing streamed yet and no instance
        forced, picks again."""
        return self._stream(request, instance_id, repick=True)

    def direct(self, request: Context, instance_id: int) -> AsyncIterator[Any]:
        """`instance_id`'s stream; a dead worker is marked and raised."""
        return self._stream(request, instance_id, repick=False)

    async def _open(
        self, request: Context, instance_id: int | None, repick: bool
    ) -> tuple[Instance, AsyncIterator[Any]]:
        from dynamo_tpu.llm.protocols.common import WorkerDiedError

        tried: set[int] = set()
        while True:
            if repick:
                with tracer().span(request.id, "route"):
                    instance = await self._pick(
                        request.payload, instance_id, request_id=request.id,
                        exclude=tried or None,
                    )
            else:
                instance = await self._pick(request.payload, instance_id)
            try:
                return instance, await self._dispatch(instance, request)
            except (
                ConnectionError, OSError,
                asyncio.TimeoutError, TimeoutError,
            ) as exc:
                # Dispatch-time connection failure: the worker is dead at
                # the seam (connection-refused class). Mark it, and —
                # since NOTHING has streamed yet — re-pick transparently.
                self.mark_dead(
                    instance.instance_id, f"dispatch:{type(exc).__name__}"
                )
                if not repick:
                    raise
                tried.add(instance.instance_id)
                if instance_id is not None or len(tried) >= MAX_DISPATCH_ATTEMPTS:
                    raise WorkerDiedError(
                        f"dispatch to {instance.instance_id:#x} failed: "
                        f"{exc}"
                    ) from exc

    async def _stream(
        self, request: Context, instance_id: int | None, repick: bool
    ) -> AsyncIterator[Any]:
        """The one generator between the router's caller and the
        instance's frames: open, then relay."""
        from dynamo_tpu.llm.protocols.common import WorkerDiedError

        instance, frames = await self._open(request, instance_id, repick)
        if repick:
            request.annotations["worker_id"] = instance.instance_id
        touch = tracer().touch
        rid = request.id
        try:
            async for item in frames:
                if request.is_killed:
                    break
                # Each streamed frame proves the request is alive: refresh
                # the frontend capture's TTL so a stream outliving ttl_s is
                # not reaped (and falsely counted abandoned) mid-flight.
                touch(rid)
                yield item
        except WorkerDiedError as exc:
            # Mid-stream death: evict + poison NOW so the failover
            # re-dispatch (and every other request) stops routing here.
            # ONLY on transport evidence — a WorkerDiedError that crossed
            # as an error FRAME was delivered by a live worker (a
            # worker-local transient, e.g. a disagg pull reset): it still
            # fails over, but evicting the reporter and pruning its radix
            # blocks would punish the fleet for nothing.
            if getattr(exc, "transport_dead", False):
                self.mark_dead(instance.instance_id, "stream")
            raise
        finally:
            # A caller that leaves early (a client gone, a stop string
            # hit) ends a local call's engine stream now, not when the
            # collector finds the generator.
            await frames.aclose()

    async def _dispatch(self, instance: Instance, request: Context):
        """Hand the request to `instance` and return its stream of
        frames (an async generator), each as msgpack delivers it. An instance this runtime
        serves and offers is called directly; any other gets the request
        envelope published and its response connection awaited (the
        dispatch ack). Raises the typed transport error on an injected
        ``fleet.worker_kill`` fault, a dead subject (NoSubscriberError)
        or a connect-back that never arrives — the three faces of 'the
        worker is a corpse'."""
        if FAULTS.active:
            await FAULTS.maybe_fail_async("fleet.worker_kill")
        served = self._drt.local_instances.get(instance.subject)
        if served is not None:
            FAILOVER.note_dispatch("local")
            return served.local_stream(request)
        FAILOVER.note_dispatch("wire")
        server = await self._drt.tcp_server()
        stream_id = uuid.uuid4().hex
        receiver = server.register(stream_id)
        envelope = {
            "id": request.id,
            "payload": request.payload,
            "resp": server.connection_info(stream_id).to_wire(),
            # Trace identity at the envelope level too: payloads that are
            # not a PreprocessedRequest wire (embeddings, raw dicts) still
            # join the request's cross-process timeline, and the worker's
            # error-plane frames stay attributable to this trace.
            "trace": tracer().context_wire(request.id, parent_span="route"),
        }
        try:
            await self._drt.bus.publish(
                instance.subject, msgpack.packb(envelope),
                require_subscriber=True,
            )
            await asyncio.wait_for(
                receiver.connected.wait(), self.connect_timeout_s
            )
        except BaseException:
            server.unregister(stream_id)
            raise
        return _unpacked(receiver)


async def _unpacked(receiver) -> AsyncIterator[Any]:
    """The wire's response frames, decoded."""
    async for payload in receiver:
        yield msgpack.unpackb(payload)
