"""Minimal protobuf *wire format* codec (proto2), no protoc required.

Used for binary compatibility with the reference's serialized artifacts:
``Datum`` records inside LMDB/LevelDB databases, ``BlobProto`` mean files,
``.caffemodel`` nets and ``.solverstate`` snapshots
(schema: ``/root/reference/src/caffe/proto/caffe.proto``).

Only the wire-level primitives plus hand-rolled (de)serializers for the handful
of messages we exchange with Caffe-format files — plus the length-prefixed
socket framing shared by every host-driven socket tier (the async-SSP
parameter service and the serving front-end).
"""

from __future__ import annotations

import io
import pickle
import socket
import struct
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

WIRETYPE_VARINT = 0
WIRETYPE_64BIT = 1
WIRETYPE_LEN = 2
WIRETYPE_32BIT = 5


class WireError(ValueError):
    pass


# --------------------------------------------------------------------------- #
# Length-prefixed socket framing (the host socket tier's wire format):
# 8-byte big-endian length + pickled payload over TCP on the launcher's
# control network (trusted, same trust domain as jax.distributed's own
# channel). Containment contract: a malformed or truncated frame raises
# FrameError so the receiving service can log and drop ONE connection
# instead of dying in its handler.
# --------------------------------------------------------------------------- #

class FrameError(ConnectionError):
    """Malformed or truncated wire frame (mid-message EOF, oversized
    length, undecodable pickle). A ConnectionError subclass so client
    recovery treats it like any other dead-channel signal, while the
    service can log it distinctly instead of dying in the handler."""


class FrameTooLargeError(ValueError):
    """SEND-side refusal of an over-cap frame. Deliberately NOT a
    ConnectionError/FrameError: the failure is deterministic and local
    (re-dialing and re-sending the same oversized pickle can never
    succeed), so it must surface loudly to the caller immediately —
    reconnect-and-replay machinery retrying it for the whole backoff
    deadline would bury the one error message that names the fix
    (POSEIDON_MAX_FRAME_BYTES on both ends)."""


# A garbage 8-byte header read as a length is astronomically large (ASCII
# bytes decode to ~10^16); cap frames so it fails fast as a FrameError
# BEFORE any allocation instead of an attempted multi-petabyte recv. The
# cap is configurable (PROTO207 found the original hard-coded 1<<32: a
# hostile or corrupt header still bought a multi-gigabyte allocation
# attempt): the default 1 GiB comfortably covers the largest real frame
# (a dense AlexNet anchor pull is ~240 MB) while an LM-sized deployment
# can raise it explicitly — a deliberate capacity decision, never a
# garbage header's.
DEFAULT_MAX_FRAME = 1 << 30          # 1 GiB
MAX_FRAME_ENV = "POSEIDON_MAX_FRAME_BYTES"
_max_frame_override: Optional[int] = None


def max_frame_bytes() -> int:
    """The active frame cap: explicit :func:`set_max_frame_bytes` wins,
    then the ``POSEIDON_MAX_FRAME_BYTES`` env (the launcher's channel,
    same distribution as the auth token), then the 1 GiB default."""
    if _max_frame_override is not None:
        return _max_frame_override
    import os
    env = os.environ.get(MAX_FRAME_ENV)
    if env:
        try:
            n = int(env)
        except ValueError:
            n = -1
        if n > 0:
            return n
        # an operator who SET the knob must not be silently told to set
        # it: warn once (warnings dedups) and fall back to the default
        import warnings
        warnings.warn(
            f"{MAX_FRAME_ENV}={env!r} is not a positive integer byte "
            f"count; using the default {DEFAULT_MAX_FRAME}",
            RuntimeWarning, stacklevel=2)
    return DEFAULT_MAX_FRAME


def set_max_frame_bytes(n: Optional[int]) -> None:
    """Process-wide override (None restores env/default resolution)."""
    global _max_frame_override
    if n is not None and n <= 0:
        raise ValueError(f"frame cap must be positive, got {n}")
    _max_frame_override = n


# --------------------------------------------------------------------------- #
# Zero-copy binary tensor codec (wire codec v1). A codec payload is
#
#   CODEC_MAGIC(4) | u32 skeleton_len | skeleton | raw tensor buffers
#
# The skeleton is a tiny pickle-free tag encoding of the message tree
# (dicts/lists/tuples/scalars); every ndarray leaf is replaced by a
# dtype-name + shape reference, and the array BYTES travel after the
# skeleton, concatenated in reference order — offsets are implied by the
# cumulative dtype/shape sizes, so there is no offset table to trust.
# Send is scatter-gather (``sendmsg`` over memoryviews of the live
# arrays — no serialization copy); receive fills ONE preallocated
# buffer sized by the cap-checked length prefix, and decoded arrays are
# ``np.frombuffer`` views into it (zero-copy; the buffer lives as long
# as any view). CODEC_MAGIC cannot collide with a pickle payload (those
# start with b"\x80"), so a receiver auto-detects the codec per frame
# and old-peer pickle frames keep working unchanged. Whether a SENDER
# may use the codec is negotiated per connection (the "wire" message
# kind in the async-SSP and serving tiers) and recorded here in a
# process-wide WeakSet of sockets. Byte order is native little-endian
# on both ends (the x86/TPU-host fleet; the skeleton itself is
# endian-explicit).
# --------------------------------------------------------------------------- #

CODEC_MAGIC = b"PTC\x01"        # version baked into the 4th byte
WIRE_CODEC_VERSION = 1
WIRE_CODEC_ENV = "POSEIDON_WIRE_CODEC"
_codec_override: Optional[bool] = None
# sockets whose PEER affirmed the codec during negotiation; WeakSet so a
# closed socket's entry dies with it (no unbounded registry growth)
_codec_socks: "weakref.WeakSet" = weakref.WeakSet()

_wire_stats_lock = threading.Lock()
_wire_stats = {
    "frames_encoded": 0, "encode_ns": 0, "encoded_bytes": 0,
    "frames_decoded": 0, "decode_ns": 0, "decoded_bytes": 0,
    "pickle_frames_sent": 0, "pickle_frames_recv": 0,
}


def wire_stats() -> Dict[str, int]:
    """Process-wide codec telemetry (encode/decode time and bytes).
    Timers cover ONLY (de)serialization — socket time is excluded, so
    the numbers compare against link transfer time directly."""
    with _wire_stats_lock:
        return dict(_wire_stats)


def wire_codec_enabled() -> bool:
    """Codec kill-switch: explicit :func:`set_wire_codec` wins, then the
    ``POSEIDON_WIRE_CODEC`` env, then ON. Off means negotiation is never
    offered/accepted and every frame is byte-for-byte the pickle wire."""
    if _codec_override is not None:
        return _codec_override
    import os
    env = os.environ.get(WIRE_CODEC_ENV)
    if env is not None:
        return env.strip().lower() not in ("0", "off", "false", "no", "")
    return True


def set_wire_codec(on: Optional[bool]) -> None:
    """Process-wide codec override (None restores env/default)."""
    global _codec_override
    _codec_override = on


def mark_codec_socket(sock: socket.socket) -> None:
    """Record that the peer on ``sock`` negotiated wire codec v1 — from
    here on :func:`send_frame` encodes this socket's frames binary."""
    _codec_socks.add(sock)


def socket_uses_codec(sock: socket.socket) -> bool:
    return sock in _codec_socks


class _CodecUnsupported(Exception):
    """Message contains something the skeleton cannot carry — the frame
    falls back to whole-message pickle (auto-detected by the receiver)."""


_dtype_name_cache: Dict[str, Optional[np.dtype]] = {}


def _dtype_from_name(name: str) -> np.dtype:
    """Resolve a wire dtype NAME (names, not ``.str``, because extension
    dtypes like bfloat16 all stringify as ``<V2``)."""
    dt = _dtype_name_cache.get(name)
    if dt is None:
        try:
            dt = np.dtype(name)
        except TypeError:
            # extension dtypes register their names only once their
            # package is imported (ml_dtypes for the bf16 wire)
            import ml_dtypes  # noqa: F401
            dt = np.dtype(name)
        _dtype_name_cache[name] = dt
    return dt


def _dtype_wire_ok(dt: np.dtype) -> bool:
    """A dtype rides the codec iff its NAME round-trips to itself."""
    ok = _dtype_name_cache.get("ok:" + dt.name)
    if ok is None:
        try:
            ok = (not dt.hasobject) and _dtype_from_name(dt.name) == dt
        except Exception:  # noqa: BLE001 — unknown name → pickle fallback
            ok = False
        _dtype_name_cache["ok:" + dt.name] = ok  # type: ignore[assignment]
    return bool(ok)


_MAX_SKELETON_DEPTH = 64


def _enc_skeleton(obj, out: bytearray, arrays: List[np.ndarray],
                  depth: int) -> None:
    if depth > _MAX_SKELETON_DEPTH:
        raise _CodecUnsupported("nesting too deep")
    if obj is None:
        out += b"N"
    elif obj is True:
        out += b"T"
    elif obj is False:
        out += b"F"
    elif type(obj) is int:
        try:
            out += b"i" + struct.pack("!q", obj)
        except struct.error:
            raise _CodecUnsupported("int out of i64 range") from None
    elif type(obj) is float:
        out += b"f" + struct.pack("!d", obj)
    elif type(obj) is str:
        raw = obj.encode("utf-8")
        out += b"s" + struct.pack("!I", len(raw))
        out += raw
    elif type(obj) is bytes:
        out += b"y" + struct.pack("!I", len(obj))
        out += obj
    elif isinstance(obj, np.ndarray):
        if not _dtype_wire_ok(obj.dtype) or obj.ndim > 255:
            raise _CodecUnsupported(f"array dtype {obj.dtype}")
        nm = obj.dtype.name.encode("ascii")
        out += b"a" + struct.pack("!B", len(nm)) + nm
        out += struct.pack("!B", obj.ndim)
        for d in obj.shape:
            out += struct.pack("!Q", d)
        arrays.append(obj)
    elif isinstance(obj, np.generic):
        dt = np.asarray(obj).dtype
        if not _dtype_wire_ok(dt):
            raise _CodecUnsupported(f"scalar dtype {dt}")
        nm = dt.name.encode("ascii")
        raw = obj.tobytes()
        out += b"z" + struct.pack("!B", len(nm)) + nm
        out += struct.pack("!B", len(raw))
        out += raw
    elif type(obj) in (list, tuple):
        out += (b"l" if type(obj) is list else b"t")
        out += struct.pack("!I", len(obj))
        for item in obj:
            _enc_skeleton(item, out, arrays, depth + 1)
    elif type(obj) is dict:
        out += b"d" + struct.pack("!I", len(obj))
        for k, v in obj.items():
            _enc_skeleton(k, out, arrays, depth + 1)
            _enc_skeleton(v, out, arrays, depth + 1)
    else:
        raise _CodecUnsupported(type(obj).__name__)


def _array_wire_view(arr: np.ndarray) -> memoryview:
    """A zero-copy byte view of the array's buffer. Extension dtypes
    (bfloat16) refuse the buffer protocol directly, so view through
    uint8; a non-contiguous leaf costs one compaction copy here."""
    arr = np.ascontiguousarray(arr)
    return memoryview(arr.reshape(-1).view(np.uint8))


def encode_codec_payload(obj):
    """Encode ``obj`` as a codec payload. Returns ``(parts, nbytes)`` —
    ``parts`` is a scatter-gather list (header bytes + live array
    views, NO concatenation copy) — or None when the message holds
    something the skeleton cannot carry (caller falls back to pickle)."""
    out = bytearray()
    arrays: List[np.ndarray] = []
    try:
        _enc_skeleton(obj, out, arrays, 0)
    except _CodecUnsupported:
        return None
    if len(out) > 0xFFFFFFFF:
        return None
    parts: List = [CODEC_MAGIC + struct.pack("!I", len(out)) + bytes(out)]
    total = len(parts[0])
    for arr in arrays:
        mv = _array_wire_view(arr)
        parts.append(mv)
        total += len(mv)
    return parts, total


class _DecCursor:
    """Bounds-checked cursors over one received payload: ``pos`` walks
    the skeleton, ``data`` walks the trailing tensor region. Every read
    is length-checked BEFORE it happens — a truncated or lying skeleton
    raises FrameError instead of reading a neighbour's bytes."""

    __slots__ = ("mv", "pos", "skel_end", "data", "end")

    def __init__(self, mv: memoryview, skel_end: int):
        self.mv = mv
        self.pos = 8
        self.skel_end = skel_end
        self.data = skel_end
        self.end = len(mv)

    def take(self, n: int) -> memoryview:
        if self.pos + n > self.skel_end:
            raise FrameError("codec skeleton truncated")
        v = self.mv[self.pos:self.pos + n]
        self.pos += n
        return v

    def take_data(self, n: int) -> memoryview:
        if self.data + n > self.end:
            raise FrameError("codec tensor data truncated")
        v = self.mv[self.data:self.data + n]
        self.data += n
        return v


def _dec_skeleton(cur: _DecCursor, depth: int):
    if depth > _MAX_SKELETON_DEPTH:
        raise FrameError("codec skeleton too deep")
    tag = bytes(cur.take(1))
    if tag == b"N":
        return None
    if tag == b"T":
        return True
    if tag == b"F":
        return False
    if tag == b"i":
        return struct.unpack("!q", cur.take(8))[0]
    if tag == b"f":
        return struct.unpack("!d", cur.take(8))[0]
    if tag == b"s":
        (n,) = struct.unpack("!I", cur.take(4))
        return bytes(cur.take(n)).decode("utf-8")
    if tag == b"y":
        (n,) = struct.unpack("!I", cur.take(4))
        return bytes(cur.take(n))
    if tag == b"a":
        (nml,) = struct.unpack("!B", cur.take(1))
        dt = _dtype_from_name(bytes(cur.take(nml)).decode("ascii"))
        (nd,) = struct.unpack("!B", cur.take(1))
        shape = tuple(struct.unpack("!Q", cur.take(8))[0]
                      for _ in range(nd))
        count = 1
        for d in shape:
            count *= d
        raw = cur.take_data(count * dt.itemsize)
        # zero-copy: the array is a view into the receive buffer
        # (writable — the buffer is a per-frame bytearray, never reused)
        return np.frombuffer(raw, dtype=dt).reshape(shape)
    if tag == b"z":
        (nml,) = struct.unpack("!B", cur.take(1))
        dt = _dtype_from_name(bytes(cur.take(nml)).decode("ascii"))
        (n,) = struct.unpack("!B", cur.take(1))
        return np.frombuffer(bytes(cur.take(n)), dtype=dt)[0]
    if tag in (b"l", b"t"):
        (n,) = struct.unpack("!I", cur.take(4))
        items = [_dec_skeleton(cur, depth + 1) for _ in range(n)]
        return items if tag == b"l" else tuple(items)
    if tag == b"d":
        (n,) = struct.unpack("!I", cur.take(4))
        return {_dec_skeleton(cur, depth + 1): _dec_skeleton(cur, depth + 1)
                for _ in range(n)}
    raise FrameError(f"unknown codec skeleton tag {tag!r}")


def decode_codec_payload(buf) -> object:
    """Decode one codec payload (the receive buffer INCLUDING the magic).
    Rejects any mismatch between the skeleton's claimed tensor extents
    and the actual payload size — truncated AND oversized frames both
    raise FrameError, nothing is silently padded or dropped."""
    mv = memoryview(buf)
    if len(mv) < 8 or bytes(mv[:4]) != CODEC_MAGIC:
        raise FrameError("not a codec payload")
    (skel_len,) = struct.unpack("!I", mv[4:8])
    if 8 + skel_len > len(mv):
        raise FrameError("codec skeleton overruns frame")
    cur = _DecCursor(mv, 8 + skel_len)
    try:
        obj = _dec_skeleton(cur, 0)
    except FrameError:
        raise
    except Exception as e:  # noqa: BLE001 — any malformed skeleton
        raise FrameError(
            f"bad codec skeleton: {type(e).__name__}: {e}") from e
    if cur.pos != cur.skel_end:
        raise FrameError("codec skeleton has trailing bytes")
    if cur.data != cur.end:
        raise FrameError(
            f"codec frame size mismatch: skeleton consumed "
            f"{cur.data - cur.skel_end} tensor bytes of "
            f"{cur.end - cur.skel_end} in the frame")
    return obj


_SENDMSG_BATCH = 64  # stay far under IOV_MAX for one sendmsg call


def _sendmsg_all(sock: socket.socket, parts: List) -> None:
    """sendall() for a scatter-gather buffer list: loop ``sendmsg`` over
    ≤64-buffer batches, resuming cleanly after partial sends."""
    bufs = [p if isinstance(p, memoryview) else memoryview(p)
            for p in parts]
    if not hasattr(sock, "sendmsg"):  # exotic socket-likes: plain sends
        for b in bufs:
            sock.sendall(b)
        return
    while bufs:
        n = sock.sendmsg(bufs[:_SENDMSG_BATCH])
        while bufs and n >= len(bufs[0]):
            n -= len(bufs[0])
            bufs.pop(0)
        if bufs and n:
            bufs[0] = bufs[0][n:]


def send_frame(sock: socket.socket, obj, codec: Optional[bool] = None) -> int:
    """Send one frame; returns the ACTUAL wire bytes (header + payload) so
    bandwidth-budgeted callers (the managed-communication token bucket) can
    account what the link really carried, not an estimate. Refuses frames
    over the configured cap LOUDLY — the peer would drop the connection
    at its own cap check, and a send-side error names the knob.

    ``codec=None`` resolves per socket (set during the "wire" negotiation);
    a codec frame is the zero-copy binary tensor encoding, anything else —
    codec off, un-negotiated peer, or a message the skeleton cannot carry —
    is today's pickle wire, byte for byte."""
    if codec is None:
        codec = socket_uses_codec(sock)
    if codec and wire_codec_enabled():
        t0 = time.perf_counter_ns()
        enc = encode_codec_payload(obj)
        dt = time.perf_counter_ns() - t0
        if enc is not None:
            parts, n = enc
            cap = max_frame_bytes()
            if n > cap:
                raise FrameTooLargeError(
                    f"refusing to send a {n}-byte frame over the "
                    f"{cap}-byte cap (raise {MAX_FRAME_ENV} or "
                    f"set_max_frame_bytes on BOTH ends for frames this "
                    f"large)")
            _sendmsg_all(sock, [struct.pack("!Q", n)] + parts)
            with _wire_stats_lock:
                _wire_stats["frames_encoded"] += 1
                _wire_stats["encode_ns"] += dt
                _wire_stats["encoded_bytes"] += n
            return n + 8
    buf = io.BytesIO()
    pickle.dump(obj, buf, protocol=pickle.HIGHEST_PROTOCOL)
    data = buf.getvalue()
    cap = max_frame_bytes()
    if len(data) > cap:
        raise FrameTooLargeError(
            f"refusing to send a {len(data)}-byte frame over the "
            f"{cap}-byte cap (raise {MAX_FRAME_ENV} or "
            f"set_max_frame_bytes on BOTH ends for frames this large)")
    sock.sendall(struct.pack("!Q", len(data)) + data)
    with _wire_stats_lock:
        _wire_stats["pickle_frames_sent"] += 1
    return len(data) + 8


def recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    want = n
    while want:
        c = sock.recv(min(want, 1 << 20))
        if not c:
            if want == n:
                raise ConnectionError("peer closed")
            raise FrameError(f"mid-message EOF ({n - want}/{n} bytes)")
        chunks.append(c)
        want -= len(c)
    return b"".join(chunks)


def _recv_into_exact(sock: socket.socket, buf: bytearray) -> None:
    """Fill the whole preallocated buffer (the codec's single receive
    allocation — decoded arrays alias it, so it is fresh per frame)."""
    view = memoryview(buf)
    n = len(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], min(n - got, 1 << 20))
        if r == 0:
            raise FrameError(f"mid-message EOF in payload ({got}/{n} bytes)")
        got += r


def recv_frame_sized(sock: socket.socket):
    """Receive one frame; returns (obj, wire_bytes) — wire_bytes is the
    actual header + payload byte count, the pull-path input to the managed-
    communication bandwidth accounting. The payload buffer is allocated
    ONCE, sized by the cap-checked length prefix; codec frames are
    auto-detected by magic (pickle cannot start with it), so a receiver
    needs no negotiation state and old-peer pickle frames always work."""
    (n,) = struct.unpack("!Q", recv_exact(sock, 8))
    cap = max_frame_bytes()
    if n > cap:
        # reject BEFORE any payload allocation: a garbage or hostile
        # header must cost a log line, not a multi-gigabyte recv buffer
        raise FrameError(
            f"frame length {n} exceeds cap {cap} (garbage header, or a "
            f"legitimately huge frame — raise {MAX_FRAME_ENV} on both "
            f"ends if it is the latter)")
    payload = bytearray(n)
    try:
        _recv_into_exact(sock, payload)
    except FrameError:
        raise
    except ConnectionError as e:
        # header arrived, payload did not: mid-message, not a clean close
        raise FrameError(f"mid-message EOF in payload ({e})") from e
    if n >= len(CODEC_MAGIC) and payload[:4] == CODEC_MAGIC:
        t0 = time.perf_counter_ns()
        obj = decode_codec_payload(payload)
        with _wire_stats_lock:
            _wire_stats["frames_decoded"] += 1
            _wire_stats["decode_ns"] += time.perf_counter_ns() - t0
            _wire_stats["decoded_bytes"] += n
        return obj, n + 8
    try:
        obj = pickle.loads(bytes(payload))
    except Exception as e:  # noqa: BLE001 — any undecodable payload
        raise FrameError(f"bad frame payload: {type(e).__name__}: {e}") from e
    with _wire_stats_lock:
        _wire_stats["pickle_frames_recv"] += 1
    return obj, n + 8


def recv_frame(sock: socket.socket):
    return recv_frame_sized(sock)[0]


# --------------------------------------------------------------------------- #
# Shared-secret connection handshake. The frame payloads are PICKLES —
# arbitrary code execution for whoever can reach (or spoof) the port — so
# an auth-enabled tier authenticates every connection MUTUALLY, over raw
# bytes (never pickle), before either side's recv_frame parses a thing:
#
#   server -> client : MAGIC + nonce_s                 (challenge)
#   client -> server : HMAC(token, nonce_s) + nonce_c  (proof + challenge)
#   server -> client : HMAC(token, nonce_c + b"srv")   (proof)
#
# The server proves possession too — a spoofed/MITM'd service that only
# replays the magic cannot produce the second digest, so a worker never
# feeds bytes from an unauthenticated peer to its pickle loader either.
# Digests are compared in constant time. The token rides the launcher env
# (POSEIDON_ASYNC_TOKEN) — same trust distribution as jax.distributed's
# coordinator address.
# --------------------------------------------------------------------------- #

AUTH_MAGIC = b"PSDNAUTH"
AUTH_NONCE_LEN = 16
AUTH_DIGEST_LEN = 32  # sha256
_AUTH_SERVER_TAG = b"srv"


class AuthError(ConnectionError):
    """Handshake failed: bad token, wrong protocol bytes, or a peer that
    speaks frames at an auth-required service."""


def _hmac_digest(token: str, nonce: bytes) -> bytes:
    import hashlib
    import hmac as hmac_mod
    return hmac_mod.new(token.encode("utf-8"), nonce,
                        hashlib.sha256).digest()


def server_handshake(sock: socket.socket, token: str,
                     timeout_s: float = 5.0) -> bool:
    """Authenticate one inbound connection (and prove our own token back).
    Returns True on success; False (after which the caller must CLOSE the
    socket without reading a single frame) on any mismatch, timeout, or
    protocol violation."""
    import hmac as hmac_mod
    nonce = __import__("os").urandom(AUTH_NONCE_LEN)
    prev = sock.gettimeout()
    sock.settimeout(timeout_s)
    try:
        sock.sendall(AUTH_MAGIC + nonce)
        got = recv_exact(sock, AUTH_DIGEST_LEN + AUTH_NONCE_LEN)
        digest, nonce_c = got[:AUTH_DIGEST_LEN], got[AUTH_DIGEST_LEN:]
        if not hmac_mod.compare_digest(digest, _hmac_digest(token, nonce)):
            return False
        sock.sendall(_hmac_digest(token, nonce_c + _AUTH_SERVER_TAG))
        return True
    except (OSError, ConnectionError, socket.timeout):
        return False
    finally:
        try:
            sock.settimeout(prev)
        except OSError:
            pass


def client_handshake(sock: socket.socket, token: str,
                     timeout_s: float = 5.0) -> None:
    """Answer the server's challenge AND verify the server's proof before
    the caller parses any frame. Raises AuthError on protocol mismatch
    (e.g. the service runs without a token and sent a frame header
    instead of the challenge) or on a server that cannot prove the
    token (spoofed endpoint)."""
    import hmac as hmac_mod
    prev = sock.gettimeout()
    sock.settimeout(timeout_s)
    try:
        head = recv_exact(sock, len(AUTH_MAGIC) + AUTH_NONCE_LEN)
        if not head.startswith(AUTH_MAGIC):
            raise AuthError("peer did not offer an auth challenge "
                            "(token configured on one side only?)")
        nonce_s = head[len(AUTH_MAGIC):]
        nonce_c = __import__("os").urandom(AUTH_NONCE_LEN)
        sock.sendall(_hmac_digest(token, nonce_s) + nonce_c)
        proof = recv_exact(sock, AUTH_DIGEST_LEN)
        if not hmac_mod.compare_digest(
                proof, _hmac_digest(token, nonce_c + _AUTH_SERVER_TAG)):
            raise AuthError("peer failed to prove the shared token "
                            "(spoofed service?)")
    finally:
        try:
            sock.settimeout(prev)
        except OSError:
            pass


def _read_varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        if pos >= len(buf):
            raise WireError("truncated varint")
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 70:
            raise WireError("varint too long")


def _write_varint(out: bytearray, value: int) -> None:
    if value < 0:
        value &= (1 << 64) - 1
    while True:
        bits = value & 0x7F
        value >>= 7
        if value:
            out.append(bits | 0x80)
        else:
            out.append(bits)
            return


def iter_fields(buf: bytes):
    """Yield (field_number, wire_type, value) over a serialized message.

    LEN fields yield raw bytes; VARINT yields int; 32/64-bit yield raw ints.
    """
    pos = 0
    while pos < len(buf):
        key, pos = _read_varint(buf, pos)
        fnum, wtype = key >> 3, key & 7
        if wtype == WIRETYPE_VARINT:
            val, pos = _read_varint(buf, pos)
        elif wtype == WIRETYPE_64BIT:
            val = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wtype == WIRETYPE_LEN:
            ln, pos = _read_varint(buf, pos)
            val = buf[pos:pos + ln]
            if len(val) != ln:
                raise WireError("truncated length-delimited field")
            pos += ln
        elif wtype == WIRETYPE_32BIT:
            val = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise WireError(f"unsupported wire type {wtype}")
        yield fnum, wtype, val


def _as_float(wtype: int, val) -> float:
    if wtype == WIRETYPE_32BIT:
        return struct.unpack("<f", val.to_bytes(4, "little"))[0]
    raise WireError("expected 32-bit float field")


def _packed_floats(val: bytes) -> np.ndarray:
    return np.frombuffer(val, dtype="<f4")


def _emit_tag(out: bytearray, fnum: int, wtype: int) -> None:
    _write_varint(out, (fnum << 3) | wtype)


def emit_varint_field(out: bytearray, fnum: int, value: int) -> None:
    _emit_tag(out, fnum, WIRETYPE_VARINT)
    _write_varint(out, value)


def emit_bytes_field(out: bytearray, fnum: int, value: bytes) -> None:
    _emit_tag(out, fnum, WIRETYPE_LEN)
    _write_varint(out, len(value))
    out.extend(value)


def emit_packed_floats(out: bytearray, fnum: int, values: np.ndarray) -> None:
    emit_bytes_field(out, fnum, np.asarray(values, dtype="<f4").tobytes())


def emit_float_field(out: bytearray, fnum: int, value: float) -> None:
    _emit_tag(out, fnum, WIRETYPE_32BIT)
    out.extend(struct.pack("<f", value))


# --------------------------------------------------------------------------- #
# Datum
# --------------------------------------------------------------------------- #

@dataclass
class Datum:
    channels: int = 0
    height: int = 0
    width: int = 0
    data: bytes = b""
    label: int = 0
    float_data: Optional[np.ndarray] = None

    def to_array(self) -> np.ndarray:
        """(C, H, W) float32 array (uint8 bytes NOT mean-subtracted/scaled)."""
        if self.float_data is not None and len(self.float_data):
            return np.asarray(self.float_data, np.float32).reshape(
                self.channels, self.height, self.width)
        arr = np.frombuffer(self.data, dtype=np.uint8)
        return arr.reshape(self.channels, self.height, self.width).astype(np.float32)


def decode_datum(buf: bytes) -> Datum:
    d = Datum()
    floats: List[float] = []
    packed: Optional[np.ndarray] = None
    for fnum, wtype, val in iter_fields(buf):
        if fnum == 1:
            d.channels = val
        elif fnum == 2:
            d.height = val
        elif fnum == 3:
            d.width = val
        elif fnum == 4:
            d.data = val
        elif fnum == 5:
            d.label = val
        elif fnum == 6:
            if wtype == WIRETYPE_LEN:
                packed = _packed_floats(val)
            else:
                floats.append(_as_float(wtype, val))
    if packed is not None:
        d.float_data = packed
    elif floats:
        d.float_data = np.asarray(floats, np.float32)
    return d


def encode_datum(d: Datum) -> bytes:
    out = bytearray()
    emit_varint_field(out, 1, d.channels)
    emit_varint_field(out, 2, d.height)
    emit_varint_field(out, 3, d.width)
    if d.data:
        emit_bytes_field(out, 4, d.data)
    emit_varint_field(out, 5, d.label)
    if d.float_data is not None and len(d.float_data):
        emit_packed_floats(out, 6, d.float_data)
    return bytes(out)


# --------------------------------------------------------------------------- #
# BlobProto
# --------------------------------------------------------------------------- #

@dataclass
class BlobProtoWire:
    num: int = 0
    channels: int = 0
    height: int = 0
    width: int = 0
    data: Optional[np.ndarray] = None
    diff: Optional[np.ndarray] = None

    @property
    def shape(self) -> Tuple[int, int, int, int]:
        return (self.num, self.channels, self.height, self.width)

    def to_array(self) -> np.ndarray:
        return np.asarray(self.data, np.float32).reshape(self.shape)


def decode_blob(buf: bytes) -> BlobProtoWire:
    b = BlobProtoWire()
    data_parts: List[np.ndarray] = []
    diff_parts: List[np.ndarray] = []
    for fnum, wtype, val in iter_fields(buf):
        if fnum == 1:
            b.num = val
        elif fnum == 2:
            b.channels = val
        elif fnum == 3:
            b.height = val
        elif fnum == 4:
            b.width = val
        elif fnum == 5:
            data_parts.append(_packed_floats(val) if wtype == WIRETYPE_LEN
                              else np.asarray([_as_float(wtype, val)], np.float32))
        elif fnum == 6:
            diff_parts.append(_packed_floats(val) if wtype == WIRETYPE_LEN
                              else np.asarray([_as_float(wtype, val)], np.float32))
    if data_parts:
        b.data = np.concatenate(data_parts)
    if diff_parts:
        b.diff = np.concatenate(diff_parts)
    return b


def encode_blob(arr: np.ndarray, diff: Optional[np.ndarray] = None) -> bytes:
    from ..core.blob import nchw
    shape = nchw(tuple(arr.shape))
    out = bytearray()
    emit_varint_field(out, 1, shape[0])
    emit_varint_field(out, 2, shape[1])
    emit_varint_field(out, 3, shape[2])
    emit_varint_field(out, 4, shape[3])
    emit_packed_floats(out, 5, np.asarray(arr, np.float32).ravel())
    if diff is not None:
        emit_packed_floats(out, 6, np.asarray(diff, np.float32).ravel())
    return bytes(out)


def read_blob_file(path: str) -> np.ndarray:
    """Read a .binaryproto BlobProto file (e.g. an image-mean file)."""
    with open(path, "rb") as f:
        return decode_blob(f.read()).to_array()


# --------------------------------------------------------------------------- #
# NetParameter-level (.caffemodel): only name + layers{name,type,blobs} matter
# for weight exchange.
# --------------------------------------------------------------------------- #

@dataclass
class LayerBlobs:
    name: str
    blobs: List[BlobProtoWire] = field(default_factory=list)


def decode_caffemodel(buf: bytes) -> Dict[str, List[np.ndarray]]:
    """Extract {layer_name: [blob arrays]} from a serialized NetParameter.

    Handles the V1 `layers`(2) field; layer name is LayerParameter field 4,
    blobs are field 6.
    """
    weights: Dict[str, List[np.ndarray]] = {}
    for fnum, wtype, val in iter_fields(buf):
        if fnum == 2 and wtype == WIRETYPE_LEN:
            name = ""
            blobs: List[BlobProtoWire] = []
            for lf, lw, lv in iter_fields(val):
                if lf == 4 and lw == WIRETYPE_LEN:
                    name = lv.decode("utf-8", "replace")
                elif lf == 6 and lw == WIRETYPE_LEN:
                    blobs.append(decode_blob(lv))
            if name:
                weights[name] = [b.to_array() for b in blobs]
    return weights


def encode_caffemodel(net_name: str, layer_weights: Dict[str, List[np.ndarray]],
                      layer_types: Optional[Dict[str, int]] = None) -> bytes:
    """Serialize weights as a NetParameter binary that Caffe can ingest."""
    out = bytearray()
    emit_bytes_field(out, 1, net_name.encode())
    for lname, blobs in layer_weights.items():
        layer = bytearray()
        emit_bytes_field(layer, 4, lname.encode())
        if layer_types and lname in layer_types:
            emit_varint_field(layer, 5, layer_types[lname])
        for arr in blobs:
            emit_bytes_field(layer, 6, encode_blob(arr))
        emit_bytes_field(out, 2, bytes(layer))
    return bytes(out)
