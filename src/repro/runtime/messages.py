"""Typed wire protocol for the Stannis runtime (DESIGN.md §10).

Every coordinator<->worker exchange is one of the dataclasses below,
serialized as a ``(kind, field-dict)`` tuple of primitives. No closures,
lambdas or live objects ever cross a process boundary — a spawn-context
worker (which shares no memory with the coordinator) deserializes the
same bytes a thread worker does, and the socket transport
(``ipc/socket.py``) JSON-encodes them unchanged into length-prefixed
frames for cross-host runs.

The protocol (one synchronous round):

  worker     -> coordinator   Hello          once, on (re)join
  coordinator -> worker       Welcome        socket rendezvous only:
                                             the authoritative WorkerSpec
  coordinator -> worker       StepGrant      paces the round (logical clock)
  worker     -> coordinator   StepReportMsg  one per granted round
  coordinator -> worker       Retune         broadcast after a plan change
  coordinator -> worker       CheckpointRequest
  worker     -> coordinator   CheckpointAck
  coordinator -> worker       Shutdown
  worker     -> coordinator   Goodbye        best-effort, before exit

A killed or suspended worker simply stops producing ``StepReportMsg`` —
there is no failure message type (only a worker that cannot serve at
all says why, in its ``Goodbye``). Liveness is *derived* from that
silence by the control plane, exactly as on the simulator's bus.

Wire shape: ``to_wire`` yields ``(kind, {field: value})`` built from a
flat per-class field tuple (computed once at registration) — NOT
``dataclasses.asdict``, which deep-copies every field recursively on
every send and was measurable on the transport hot path. Field values
are therefore shared, not copied: senders must treat a message as
frozen once ``put`` — which every call site already did. Fields listed
in ``wire_optional`` are omitted from the wire dict while they hold
their default value, so a NEW protocol field (e.g. the codec
negotiation fields below) never reaches an old peer that would reject
the unknown key — tests/test_wire_codec.py pins the legacy shapes.

``wire_id`` is the binary codec's one-byte kind id (DESIGN.md §13),
registered here alongside the kind string so the id space and the
class registry can never drift apart. Ids are a pinned public
contract: never renumber, only append.

``seq`` (DESIGN.md §15) is the per-channel session sequence number the
reliable session layer (``ipc/session.py``) stamps onto frames when a
chaos-hardened channel is negotiated: -1 (the default) means
"unsequenced" and is omitted from the wire, so every message a normal
run produces is byte-identical to the pre-chaos protocol under every
codec — the binary codecs drop trailing ``wire_tail`` fields at their
default for exactly this reason. Receivers that never sequence simply
ignore the field.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar, Dict, List, Optional, Tuple, Type

_REGISTRY: Dict[str, Type["Message"]] = {}
_WIRE_IDS: Dict[int, Type["Message"]] = {}

WireMessage = Tuple[str, Dict]


def register(cls: Type["Message"]) -> Type["Message"]:
    if cls.wire_id in _WIRE_IDS:
        raise ValueError(
            f"wire_id {cls.wire_id} of {cls.__name__} already taken by "
            f"{_WIRE_IDS[cls.wire_id].__name__}")
    _REGISTRY[cls.kind] = cls
    _WIRE_IDS[cls.wire_id] = cls
    # the flat wire schema, computed once: field order is the binary
    # codec's tuple order, defaults let optional fields travel omitted
    cls._fields = tuple(f.name for f in dataclasses.fields(cls))
    cls._defaults = {
        f.name: (f.default_factory() if f.default_factory
                 is not dataclasses.MISSING else f.default)
        for f in dataclasses.fields(cls) if f.name in cls.wire_optional}
    return cls


@dataclasses.dataclass
class Message:
    """Base wire message. Subclasses set a unique ``kind`` ClassVar and
    a unique one-byte ``wire_id``."""

    kind: ClassVar[str] = "base"
    wire_id: ClassVar[int] = 0
    # fields omitted from the wire dict while at their default — ONLY
    # for fields added after a wire shape became a public contract
    wire_optional: ClassVar[frozenset] = frozenset()
    # the subset of wire_optional the BINARY codecs may drop from the
    # flat value tuple while trailing AND at their default — how a
    # late-added field (seq) keeps pinned binary frames byte-identical
    wire_tail: ClassVar[frozenset] = frozenset({"seq"})
    _fields: ClassVar[Tuple[str, ...]] = ()
    _defaults: ClassVar[Dict] = {}

    def to_wire(self) -> WireMessage:
        if self.wire_optional:
            return (self.kind,
                    {n: getattr(self, n) for n in self._fields
                     if n not in self._defaults
                     or getattr(self, n) != self._defaults[n]})
        return (self.kind, {n: getattr(self, n) for n in self._fields})

    @staticmethod
    def from_wire(wire: WireMessage) -> "Message":
        kind, fields = wire
        return _REGISTRY[kind](**fields)


@register
@dataclasses.dataclass
class Hello(Message):
    """Worker announces itself (join / rejoin). ``incarnation`` counts
    restarts so the coordinator can tell a rejoined worker from a stale
    late message of its previous life. ``host``/``endpoint`` carry the
    worker's identity on a multi-host mesh (hostname and its side of
    the transport, e.g. ``"10.0.0.7:51312"`` for a socket worker) —
    empty for the in-process transports, where the identity is the
    process itself.

    ``codecs`` is the codec offer (DESIGN.md §13): the wire-codec names
    this worker can speak, preference-ordered. Omitted from the wire
    while empty, so an old worker's Hello and a new worker's Hello to
    an old coordinator are both the legacy shape — an empty offer means
    "json only", which is how old workers keep joining a binary-default
    coordinator."""

    kind: ClassVar[str] = "hello"
    wire_id: ClassVar[int] = 1
    wire_optional: ClassVar[frozenset] = frozenset({"codecs", "seq"})
    group: str
    pid: int
    batch_size: int
    incarnation: int = 0
    host: str = ""
    endpoint: str = ""
    codecs: List[str] = dataclasses.field(default_factory=list)
    seq: int = -1


@register
@dataclasses.dataclass
class Welcome(Message):
    """Coordinator's reply to a socket worker's join-request Hello: the
    authoritative :class:`~repro.runtime.worker.WorkerSpec` as wire
    primitives, including the incarnation the coordinator assigns.
    Standalone workers (``python -m repro.launch.worker --connect``)
    join knowing only their group name and learn everything else —
    batch size, speed tables, fault schedule — from this message, so a
    real multi-host run needs no shared filesystem. The in-process
    transports never send it (their specs travel at spawn time).

    ``codec`` is the coordinator's pick from the worker's Hello offer
    (DESIGN.md §13). The rendezvous itself is always spoken in json —
    the compatibility baseline — and BOTH ends switch to the chosen
    codec immediately after this message: the coordinator right after
    sending it, the worker right after receiving it, so the channel is
    never ambiguous mid-stream (the protocol is strictly alternating
    until here). Omitted while "json" so a worker that never offered
    (an old build) receives the exact legacy Welcome shape."""

    kind: ClassVar[str] = "welcome"
    wire_id: ClassVar[int] = 2
    wire_optional: ClassVar[frozenset] = frozenset({"codec", "seq"})
    spec: Dict
    codec: str = "json"
    seq: int = -1


@register
@dataclasses.dataclass
class StepGrant(Message):
    """Coordinator paces one round. ``step`` is the coordinator's
    logical clock — workers stamp their report with it, so interference
    windows and liveness arithmetic align across the whole cluster
    without wall-clock agreement.

    ``staleness`` is the coordinator's bounded-staleness window k: how
    many rounds of grants it keeps in flight beyond the one it is
    currently collecting. k=0 is the strict grant -> report rendezvous
    (the synchronous mode, and the Fig. 6 parity baseline); k>=1 lets a
    worker run ahead, answering queued grants back-to-back while the
    coordinator overlaps collection of older rounds with the next
    grant. Informational for the worker — its loop is identical either
    way (drain the channel FIFO, stamp each report with the granted
    step) — but carried on the wire so a worker can reason about how
    far ahead of the control plane it may be running."""

    kind: ClassVar[str] = "grant"
    wire_id: ClassVar[int] = 3
    wire_optional: ClassVar[frozenset] = frozenset({"seq"})
    step: int
    staleness: int = 0
    seq: int = -1


@register
@dataclasses.dataclass
class StepReportMsg(Message):
    """One group's measurement for one granted round (the wire form of
    :class:`repro.core.control.telemetry.StepReport`). ``batch_size`` is
    the batch the worker ACTUALLY ran — the coordinator uses it to
    measure retune propagation lag. ``wall_dt`` is the real measured
    step time when the worker executes a jitted step.

    ``obs`` piggybacks the worker's local trace-event batch (compact
    wire lists, DESIGN.md §14) on the report it was already sending —
    observability adds no frames of its own. ``wire_optional``: omitted
    while None, so a worker that is not tracing (every legacy worker,
    and every worker whose coordinator did not ask) produces the exact
    legacy wire shape."""

    kind: ClassVar[str] = "report"
    wire_id: ClassVar[int] = 4
    wire_optional: ClassVar[frozenset] = frozenset({"obs", "seq"})
    step: int
    group: str
    speed: float
    cpu_util: Optional[float] = None
    power_w: Optional[float] = None
    batch_size: int = 0
    wall_dt: Optional[float] = None
    loss: Optional[float] = None
    obs: Optional[List] = None
    seq: int = -1


# the per-report value-list schema inside a ReportBatch frame: the
# pre-obs field set, pinned so coalesced report tuples keep their wire
# arity across the obs addition (obs rides at the batch level instead;
# seq likewise rides on the BATCH frame — sequencing is per frame, not
# per coalesced report)
REPORT_PACK_FIELDS: Tuple[str, ...] = tuple(
    n for n in StepReportMsg._fields if n not in ("obs", "seq"))


@register
@dataclasses.dataclass
class ReportBatch(Message):
    """k coalesced :class:`StepReportMsg` in one frame (DESIGN.md §13).

    Under bounded-staleness run-ahead a worker holding several granted
    rounds used to answer them as k separate frames back-to-back — k
    syscalls and k frame headers for reports the coordinator would
    bucket individually anyway. The worker loop now drains its whole
    grant backlog first and ships ONE batch; the coordinator unpacks it
    into :class:`~repro.core.control.telemetry.StepBuckets` report by
    report, in order, so ordering / staleness-floor / incarnation
    semantics are exactly those of k single frames. At staleness 0 a
    worker never holds more than one pending report and this message
    never appears on the wire — which is why the synchronous parity
    traces are bit-for-bit unchanged.

    ``reports`` is wire-flat: one value list per report, in
    ``StepReportMsg`` field order (no per-report key repetition).
    Trace-event piggybacking (DESIGN.md §14) rides at the BATCH level —
    ``obs`` is one event batch for the whole frame, set by the worker's
    flush — so the per-report value lists keep the pre-obs field set
    (:data:`REPORT_PACK_FIELDS`) and their wire arity never changes."""

    kind: ClassVar[str] = "reports"
    wire_id: ClassVar[int] = 10
    wire_optional: ClassVar[frozenset] = frozenset({"obs", "seq"})
    reports: List[List] = dataclasses.field(default_factory=list)
    obs: Optional[List] = None
    seq: int = -1

    @classmethod
    def pack(cls, msgs: List[StepReportMsg]) -> "ReportBatch":
        return cls([[getattr(m, n) for n in REPORT_PACK_FIELDS]
                    for m in msgs])

    def unpack(self) -> List[StepReportMsg]:
        return [StepReportMsg(*values) for values in self.reports]


@register
@dataclasses.dataclass
class Retune(Message):
    """Plan change pushed to every live worker: the full new per-group
    batch map (workers pick their own entry and flip their row mask —
    no recompilation, DESIGN.md §2)."""

    kind: ClassVar[str] = "retune"
    wire_id: ClassVar[int] = 5
    wire_optional: ClassVar[frozenset] = frozenset({"seq"})
    step: int
    batch_sizes: Dict[str, int]
    group: str = ""                      # group that triggered the change
    reason: str = ""
    seq: int = -1


@register
@dataclasses.dataclass
class CheckpointRequest(Message):
    kind: ClassVar[str] = "ckpt_req"
    wire_id: ClassVar[int] = 6
    wire_optional: ClassVar[frozenset] = frozenset({"seq"})
    step: int
    seq: int = -1


@register
@dataclasses.dataclass
class CheckpointAck(Message):
    """Worker-side state summary. ``n_compiles`` proves the no-recompile
    retune invariant end-to-end (it must stay at 1 across retunes).

    ``state`` is the bulk state blob as a *bulk reference* (DESIGN.md
    §13): ``["inline", <base64 str>]`` for cross-host peers, or
    ``["shm", name, offset, length, seq]`` pointing into the worker's
    shared-memory ring for a same-host coordinator — the control frame
    stays small either way. The event loop resolves it to raw bytes
    (``repro.runtime.ipc.shm.resolve_bulk``) before the ack is stored,
    so consumers of ``RuntimeResult.checkpoint_acks`` always see the
    inline form. Omitted from the wire while None (legacy shape)."""

    kind: ClassVar[str] = "ckpt_ack"
    wire_id: ClassVar[int] = 7
    wire_optional: ClassVar[frozenset] = frozenset({"state", "obs", "seq"})
    step: int
    group: str
    worker_step: int
    batch_size: int
    n_compiles: int = 0
    state: Optional[List] = None
    # trace-event piggyback (DESIGN.md §14): acks carry whatever the
    # worker traced since its last report flush, so ack-only traffic
    # (e.g. the final drain) still ships its events. Omitted while None.
    obs: Optional[List] = None
    seq: int = -1


@register
@dataclasses.dataclass
class Shutdown(Message):
    kind: ClassVar[str] = "shutdown"
    wire_id: ClassVar[int] = 8
    wire_optional: ClassVar[frozenset] = frozenset({"seq"})
    reason: str = "done"
    seq: int = -1


@register
@dataclasses.dataclass
class Goodbye(Message):
    """Best-effort farewell. ``error`` is set when the worker could not
    serve at all (its training executor failed to build): the
    coordinator fails the run instead of reading the exit as a dropout.
    Omitted from the wire while empty (legacy shape)."""

    kind: ClassVar[str] = "goodbye"
    wire_id: ClassVar[int] = 9
    wire_optional: ClassVar[frozenset] = frozenset({"seq", "error"})
    wire_tail: ClassVar[frozenset] = frozenset({"seq", "error"})
    group: str
    worker_step: int
    seq: int = -1
    error: str = ""


@register
@dataclasses.dataclass
class SessionAck(Message):
    """Cumulative acknowledgement of the reliable session layer
    (``ipc/session.py``, DESIGN.md §15): "I have delivered every frame
    with ``seq <= ack`` in order". Doubles as the gap re-request — a
    receiver that detects a hole re-sends its current cumulative ack
    immediately, and the sender treats a duplicate ack as a NAK for
    ``ack + 1`` (fast retransmit). Never itself sequenced, so the ack
    channel can never deadlock behind the data it acknowledges. Only a
    chaos-negotiated channel ever carries this kind — normal runs are
    byte-identical to the pre-chaos protocol."""

    kind: ClassVar[str] = "session_ack"
    wire_id: ClassVar[int] = 11
    ack: int
