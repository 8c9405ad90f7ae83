"""Wire and persistence message schema.

Mirrors the reference protobuf schema field-for-field
(/root/reference/smartbftprotos/messages.proto:14-129,
/root/reference/smartbftprotos/logrecord.proto:13-24) but encoded with the
canonical deterministic codec in :mod:`smartbft_tpu.codec` instead of
protobuf.  The top-level consensus ``Message`` oneof becomes the 1-byte tag
union of the ten message classes; ``SavedMessage`` (the WAL payload oneof)
likewise.

All integers are unsigned 64-bit.  ``digest`` fields are ``str`` (hex), as in
the reference.  Registration order below fixes the wire tags — append only.
"""

from __future__ import annotations

from typing import Optional, Union

from .codec import (
    decode,
    decode_tagged,
    encode,
    encode_tagged,
    wiremsg,
)


@wiremsg
class Signature:
    signer: int = 0
    value: bytes = b""
    msg: bytes = b""


@wiremsg
class Proposal:
    header: bytes = b""
    payload: bytes = b""
    metadata: bytes = b""
    verification_sequence: int = 0


@wiremsg
class ViewMetadata:
    view_id: int = 0
    latest_sequence: int = 0
    decisions_in_view: int = 0
    black_list: list[int] = None  # type: ignore[assignment]
    prev_commit_signature_digest: bytes = b""

    def __post_init__(self):
        if self.black_list is None:
            object.__setattr__(self, "black_list", [])


@wiremsg
class PrePrepare:
    view: int = 0
    seq: int = 0
    proposal: Optional[Proposal] = None
    prev_commit_signatures: list[Signature] = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.prev_commit_signatures is None:
            object.__setattr__(self, "prev_commit_signatures", [])


@wiremsg
class Prepare:
    view: int = 0
    seq: int = 0
    digest: str = ""
    assist: bool = False


@wiremsg
class Commit:
    view: int = 0
    seq: int = 0
    digest: str = ""
    signature: Optional[Signature] = None
    assist: bool = False


@wiremsg
class PreparesFrom:
    ids: list[int] = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.ids is None:
            object.__setattr__(self, "ids", [])


@wiremsg
class ViewChange:
    next_view: int = 0
    reason: str = ""


@wiremsg
class ViewData:
    next_view: int = 0
    last_decision: Optional[Proposal] = None
    last_decision_signatures: list[Signature] = None  # type: ignore[assignment]
    in_flight_proposal: Optional[Proposal] = None
    in_flight_prepared: bool = False
    # Pipelined-window extension (pipeline_depth > 1, no reference
    # counterpart): the in-flight LADDER above the singular rung.
    # ``in_flight_proposal`` remains the rung at last_decision_seq+1, so all
    # single-slot validation applies unchanged; ``in_flight_more[i]`` is the
    # rung at last_decision_seq+2+i with ``in_flight_more_prepared[i]``.
    in_flight_more: list[Proposal] = None  # type: ignore[assignment]
    in_flight_more_prepared: list[bool] = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.last_decision_signatures is None:
            object.__setattr__(self, "last_decision_signatures", [])
        if self.in_flight_more is None:
            object.__setattr__(self, "in_flight_more", [])
        if self.in_flight_more_prepared is None:
            object.__setattr__(self, "in_flight_more_prepared", [])


@wiremsg
class SignedViewData:
    raw_view_data: bytes = b""
    signer: int = 0
    signature: bytes = b""


@wiremsg
class NewView:
    signed_view_data: list[SignedViewData] = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.signed_view_data is None:
            object.__setattr__(self, "signed_view_data", [])


@wiremsg
class HeartBeat:
    view: int = 0
    seq: int = 0


@wiremsg
class HeartBeatResponse:
    view: int = 0


@wiremsg
class StateTransferRequest:
    """Empty in the reference schema (messages.proto:122-124)."""


@wiremsg
class StateTransferResponse:
    view_num: int = 0
    sequence: int = 0


#: The consensus wire "oneof": any of the ten protocol messages.
Message = Union[
    PrePrepare,
    Prepare,
    Commit,
    ViewChange,
    SignedViewData,
    NewView,
    HeartBeat,
    HeartBeatResponse,
    StateTransferRequest,
    StateTransferResponse,
]

CONSENSUS_MSG_TYPES = (
    PrePrepare,
    Prepare,
    Commit,
    ViewChange,
    SignedViewData,
    NewView,
    HeartBeat,
    HeartBeatResponse,
    StateTransferRequest,
    StateTransferResponse,
)


@wiremsg
class ProposedRecord:
    pre_prepare: Optional[PrePrepare] = None
    prepare: Optional[Prepare] = None


#: WAL payload "oneof" (messages.proto:113-120): what gets persisted at each
#: phase transition.  ``CommitRecord`` wraps the commit message; ``NewViewRecord``
#: stores the adopted ViewMetadata.
@wiremsg
class CommitRecord:
    commit: Optional[Commit] = None


@wiremsg
class NewViewRecord:
    metadata: Optional[ViewMetadata] = None


@wiremsg
class ViewChangeRecord:
    view_change: Optional[ViewChange] = None


SavedMessage = Union[ProposedRecord, CommitRecord, NewViewRecord, ViewChangeRecord]

SAVED_MSG_TYPES = (ProposedRecord, CommitRecord, NewViewRecord, ViewChangeRecord)


def marshal(msg) -> bytes:
    """Tagged canonical encoding — the wire format for Comm and the WAL."""
    return encode_tagged(msg)


def unmarshal(data: bytes):
    return decode_tagged(data)


def marshal_untagged(msg) -> bytes:
    return encode(msg)


def unmarshal_as(cls, data: bytes):
    return decode(cls, data)


# ---------------------------------------------------------------------------
# Vectorized message plane: encode-once + interned decode.
#
# A broadcast used to pay one encode per recipient and one decode per
# delivery (n-1 each at fan-out n).  ``wire_of`` memoizes the canonical
# encoding ON the frozen message instance, so a broadcast (and every
# re-broadcast/assist resend of the same object) encodes at most once;
# ``unmarshal_interned`` memoizes decode BY WIRE BYTES in a bounded LRU, so
# the n-1 identical deliveries of one broadcast decode once and every
# recipient shares the same frozen message object.  The contract that makes
# the sharing sound: ingested messages are IMMUTABLE — receivers never
# mutate a decoded message (wiremsg dataclasses are frozen; protocol code
# copies nested lists before touching them), and fault injection that wants
# to corrupt a message must deep-copy it first (``deep_copy_message``).
# ---------------------------------------------------------------------------

from time import perf_counter as _perf_counter  # noqa: E402

from .metrics import PROTOCOL_PLANE as _PLANE  # noqa: E402
from .obs.recorder import PROCESS as _REC  # noqa: E402
from .utils.memo import LruMemo  # noqa: E402

_WIRE_MEMO_ATTR = "_wire_memo"

#: default bound for the tagged-decode intern memo: comfortably above the
#: live window of any cluster this harness runs (3k slots x a few message
#: kinds x n senders collapse to one entry per distinct broadcast), small
#: enough that a Byzantine flood of unique messages cannot grow memory
INTERN_MEMO_BOUND = 4096


def _count_intern_eviction() -> None:
    _PLANE.intern_evictions += 1


_INTERN: LruMemo[bytes, object] = LruMemo(
    INTERN_MEMO_BOUND, on_evict=_count_intern_eviction
)


def wire_of(msg, plane=None) -> bytes:
    """Canonical tagged encoding, memoized on the (frozen) instance.

    The memo makes "exactly one encode per broadcast" a structural
    invariant: the fan-out loop, re-broadcasts after view restarts, and
    lagging-replica assist resends all reuse the first encoding.

    ``plane``: the :class:`~smartbft_tpu.metrics.ProtocolPlaneTimers` the
    codec cost is attributed to — per-shard planes in sharded mode; the
    process default otherwise."""
    plane = _PLANE if plane is None else plane
    w = getattr(msg, _WIRE_MEMO_ATTR, None)
    if w is None:
        # busy span: one per actual encode (once per broadcast)
        span = _REC.begin("codec") if _REC.enabled else None
        t0 = _perf_counter()
        w = encode_tagged(msg)
        plane.codec_us += (_perf_counter() - t0) * 1e6
        if span is not None:
            _REC.end(span)
        plane.encodes += 1
        object.__setattr__(msg, _WIRE_MEMO_ATTR, w)
    else:
        plane.encode_memo_hits += 1
    return w


def unmarshal_interned(data: bytes, plane=None):
    """Tagged decode through the bounded intern memo.

    All recipients of one broadcast receive byte-identical wire payloads,
    so the first delivery decodes and every later one is a dict hit
    returning the SAME frozen message object — receivers must treat it as
    immutable.  The memo is LRU-bounded (eviction counted in
    ``metrics.PROTOCOL_PLANE.intern_evictions``), so unique-message floods
    cannot grow memory.  ``plane``: see :func:`wire_of` — the intern memo
    itself stays process-wide (it is keyed by wire bytes, which cannot
    collide across shards), only the accounting is attributed."""
    plane = _PLANE if plane is None else plane
    msg = _INTERN.get(data)
    if msg is not None:
        plane.decode_interned_hits += 1
        return msg
    # busy span: one per actual decode (an intern miss; a malformed
    # payload raises through it)
    span = _REC.begin("codec") if _REC.enabled else None
    try:
        t0 = _perf_counter()
        msg = decode_tagged(data)
        plane.codec_us += (_perf_counter() - t0) * 1e6
    finally:
        if span is not None:
            _REC.end(span)
    plane.decodes += 1
    # the decoded object already knows its own encoding — assists and
    # forwards of an ingested message re-send without re-encoding
    object.__setattr__(msg, _WIRE_MEMO_ATTR, data)
    _INTERN.put(data, msg)
    return msg


def intern_memo_len() -> int:
    return len(_INTERN)


def clear_intern_memo() -> None:
    _INTERN.clear()


def deep_copy_message(msg):
    """A genuinely fresh copy of a wire message (codec round-trip).

    For fault injection that MUTATES messages: broadcasts share one frozen
    decoded object across all recipients, so in-place corruption of the
    shared instance would leak into every replica's ingest.  A codec
    round-trip yields an independent object tree with none of the cached
    derivations (`_wire_memo`, `_digest_memo`) that an in-place mutation
    would otherwise leave stale."""
    return decode_tagged(encode_tagged(msg))
