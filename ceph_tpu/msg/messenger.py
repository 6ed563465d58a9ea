"""Threaded TCP transport with dispatcher chain and fault injection.

Role of the reference's Messenger/AsyncMessenger (src/msg/Messenger.h,
src/msg/async/): daemons bind a listening address, connections carry
ordered typed messages, incoming messages walk a dispatcher chain
(Dispatcher::ms_dispatch, first taker wins), and per-peer policy decides
lossy vs lossless (reconnect + resend) behavior. The reference runs
epoll worker threads; here each connection has a writer queue + reader
thread — same ordering and failure semantics at framework scale.

Fault injection mirrors `ms inject socket failures` (qa msgr-failures
fragments): drop 1-in-N messages, add bounded random delivery delay.

Framing: 4-byte magic, 4-byte length, versioned binary encoding of the
typed Message (ceph_tpu.encoding — no pickle: inbound bytes can only
materialize the closed set of registered types, never run code).
Connection auth is the cephx authorizer handshake with a mandatory
per-connection server challenge (the reference's
CephxAuthorizeChallenge): BANNER -> BANNER_RETRY(challenge) ->
BANNER(challenge proof) -> BANNER_ACK(mutual-auth proof). Pre-auth
frames on a guarded connection are parsed in restricted mode (builtins
only) and anything but the handshake drops the connection.
"""

from __future__ import annotations

import hashlib
import hmac
import os
import random
import socket
import struct
import threading
import time

from .. import encoding

__all__ = ["EntityAddr", "Dispatcher", "Messenger", "Connection"]

_MAGIC = b"CTPU"
# frame header: magic, payload length, link_seq, signature. The
# per-connection sequence rides the FRAME, not the message object: one
# message object may be queued to several peers at once, and stamping a
# shared object per-connection would race (a frame could carry another
# pipe's seq, making the receiver's dedup drop later messages as
# duplicates). seq 0 = control frame (handshake, acks) — unsequenced.
# sig: truncated HMAC-SHA256 over (sender nonce, magic, len, seq,
# payload) under the connection's cephx session key — the reference's
# per-message signing (CephxSessionHandler::sign_message keeps a u64
# signature in the footer the same way). The sender's SESSION NONCE in
# the MAC binds direction: both directions share one session key, so
# without it a MITM could reflect a signed frame back at its
# originator. 0 = unsigned (pre-auth / signing off).
_HDR = struct.Struct("<4sIQQ")


def _frame_sig(key: bytes, sender_nonce: str, length: int, seq: int,
               payload: bytes) -> int:
    mac = hmac.new(key,
                   (sender_nonce or "").encode()
                   + _HDR.pack(_MAGIC, length, seq, 0) + payload,
                   hashlib.sha256).digest()
    sig = struct.unpack("<Q", mac[:8])[0]
    return sig or 1   # 0 means "unsigned" on the wire


class EntityAddr(tuple):
    """(host, port); tuple so it compares naturally."""

    def __new__(cls, host: str, port: int):
        return super().__new__(cls, (host, port))

    def __getnewargs__(self):
        # tuple subclass with a (host, port) __new__: tell pickle to
        # call it with two args, not one tuple
        return (self[0], self[1])

    @property
    def host(self):
        return self[0]

    @property
    def port(self):
        return self[1]


class Dispatcher:
    """ms_dispatch contract (src/msg/Dispatcher.h)."""

    def ms_dispatch(self, msg) -> bool:
        """Return True if this dispatcher consumed the message."""
        return False

    def ms_handle_reset(self, addr) -> None:
        """Peer connection dropped (lossy) — state cleanup hook."""


def _encode(msg, seq: int = 0, key: bytes | None = None,
            nonce: str = "") -> bytes:
    payload = encoding.encode_any(msg)
    sig = _frame_sig(key, nonce, len(payload), seq, payload) \
        if key else 0
    return _HDR.pack(_MAGIC, len(payload), seq, sig) + payload


def _read_exact(sock, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


class Connection:
    """One ordered peer link: writer queue + reader thread."""

    def __init__(self, msgr: "Messenger", peer_addr, sock=None):
        self.msgr = msgr
        self.peer_addr = peer_addr
        self.sock = sock
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        self.out_q: list = []
        # lossless ack protocol (the reference's out_seq/in_seq,
        # Pipe/AsyncConnection): a sent message stays in _unacked until
        # the peer's MSGACK covers it; reconnect requeues _unacked —
        # bytes accepted by a dying TCP buffer are NOT delivery
        self.out_seq = 0
        self._unacked: list = []      # [(link_seq, msg)]
        # reconnect resend set: (seq, msg) pairs that keep their
        # ORIGINAL link_seq on the wire — the peer's dedup identifies
        # an already-delivered resend by seq, so reassigning seqs on
        # resend (as a fresh send would) would defeat exactly-once
        self._resend: list = []
        self._ctrl_out: list = []     # reader-queued control frames
        # session identity for exactly-once delivery across reconnects
        # (the reference's connect_seq + in_seq exchange,
        # src/msg/simple/Pipe.cc connect phase): each Connection mints
        # a nonce; the dialer's rides the BANNER, the acceptor's rides
        # the BANNER_ACK, and BOTH sides track the last-delivered
        # link_seq per peer nonce at the Messenger level — resent
        # messages whose acks were lost are acked again but NOT
        # re-dispatched, in either direction.
        self.conn_nonce = os.urandom(8).hex()
        self._dedup_key = None       # the PEER's session nonce
        self._in_seq = 0             # last delivered link_seq from peer
        self.peer_name = None
        self.auth_info = None        # verified cephx info (entity, caps)
        # per-message signing key: the cephx SESSION key, armed when
        # the handshake lands (acceptor: verify_authorizer's info;
        # dialer: msgr.session_key_fn at BANNER_ACK) and cleared on
        # every pipe death — each socket re-proves itself
        self.session_key: bytes | None = None
        self.inbound = sock is not None   # accepted vs dialed
        self.auth_confirmed = False  # dialer saw a valid BANNER_ACK
        self._sent_authorizer = None
        self._server_challenge = None     # acceptor's per-conn random
        self._auth_ready = threading.Event()  # dialer handshake done
        self.closed = False
        self.writer: threading.Thread | None = None  # lazy (start())
        self.reader: threading.Thread | None = None

    def __repr__(self):
        return "<Connection peer=%s name=%s%s>" % (
            self.peer_addr, self.peer_name,
            " closed" if self.closed else "")

    def start(self) -> None:
        self.writer = threading.Thread(target=self._writer_loop,
                                       daemon=True)
        self.writer.start()
        if self.sock is not None:
            self._start_reader()

    def _start_reader(self) -> None:
        self.reader = threading.Thread(target=self._reader_loop,
                                       daemon=True)
        self.reader.start()

    def send(self, msg) -> None:
        with self.lock:
            if self.closed:
                return
            self.out_q.append(msg)
            self.cond.notify()

    # -- writer --------------------------------------------------------

    def _connect(self) -> bool:
        # Mint the authorizer outside the socket try: a failing factory
        # (no ticket yet) must read as a failed connect attempt, not kill
        # the writer thread.
        authorizer = None
        if self.msgr.authorizer_factory is not None:
            try:
                authorizer = self.msgr.authorizer_factory()
            except Exception:
                return False
        try:
            sock = socket.create_connection(tuple(self.peer_addr),
                                            timeout=5.0)
            sock.settimeout(None)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # a fresh socket means a fresh peer: mutual auth must be
            # re-proven before inbound traffic is trusted again
            self.auth_confirmed = False
            self._auth_ready.clear()
            self.session_key = None
            # banner (the msgr protocol's handshake): advertise our
            # bound address so the acceptor can route replies back over
            # this same connection (Ceph learns the peer_addr during the
            # connect handshake; replies never dial the ephemeral port)
            sock.sendall(_encode(
                ("BANNER", tuple(self.msgr.my_addr or ("", 0)),
                 self.msgr.name, authorizer, self.conn_nonce,
                 self.msgr._sign_intent())))
            self._sent_authorizer = authorizer
            self.sock = sock
            self._start_reader()
        except OSError:
            return False
        if self.msgr.auth_confirm is not None \
                or self.msgr.authorizer_factory is not None:
            # hold data until the challenge round + mutual auth land:
            # the acceptor cuts connections that send data pre-auth
            if not self._auth_ready.wait(timeout=5.0) \
                    or not self.auth_confirmed:
                try:
                    sock.close()
                except OSError:
                    pass
                if self.sock is sock:
                    self.sock = None
                return False
        # fresh pipe: everything the old one never acked goes first,
        # keeping its original link_seq (the peer dedups resends by it).
        # Lossy connections DROP instead: a lossy fault discards the
        # session (reference Pipe semantics), so stale pre-fault
        # messages must not resurface on the next connect.
        with self.lock:
            if self._unacked:
                if self.msgr.policy_lossy:
                    self._unacked.clear()
                else:
                    self._resend[0:0] = self._unacked
                    self._unacked.clear()
        return True

    def _send_key(self) -> bytes | None:
        """Signing key for outgoing frames (None = unsigned)."""
        if not self.msgr.sign_messages:
            return None
        return self.session_key

    def _encode_out(self, msg, seq: int = 0) -> bytes:
        """Outgoing frame, signed with OUR session nonce when armed
        (the receiver verifies with its _dedup_key = our nonce)."""
        key = self._send_key()
        return _encode(msg, seq, key, self.conn_nonce if key else "")

    def _verify_frame(self, payload: bytes, link_seq: int,
                      sig: int) -> bool:
        """Armed connections require a valid signature on EVERY inbound
        frame — after the handshake no legitimate unsigned frame exists
        on this socket (a reconnect is a new socket that re-arms). The
        MAC covers the SENDER's nonce (our _dedup_key), so a frame we
        signed ourselves cannot be reflected back at us."""
        if self.session_key is None or not self.msgr.sign_messages:
            return True
        want = _frame_sig(self.session_key, self._dedup_key or "",
                          len(payload), link_seq, payload)
        return hmac.compare_digest(struct.pack("<Q", sig),
                                   struct.pack("<Q", want))

    def _peer_dialable(self) -> bool:
        """The peer advertised a REAL listening address we could
        re-dial (a bind-less client advertises (\"\", 0) — dialing
        that would spin forever)."""
        return bool(self.peer_name is not None and self.peer_addr
                    and self.peer_addr[0] and self.peer_addr[1])

    @property
    def _guarded_dialer_now(self) -> bool:
        """Dialer that runs ANY part of the auth handshake and has not
        completed it — the one predicate behind the pre-auth data hold,
        the restricted decode, and the direct-send handshake phase."""
        return (not self.inbound
                and (self.msgr.auth_confirm is not None
                     or self.msgr.authorizer_factory is not None)
                and not self.auth_confirmed)

    def _queue_ctrl(self, data: bytes) -> None:
        """Reader-side protocol replies (banner acks, MSGACKs) route
        through the writer thread — two threads sendall-ing one socket
        would interleave partial writes and corrupt the framing.

        EXCEPT during the handshake, when the writer is provably not
        sending: a guarded dialer's writer is parked inside _connect
        waiting for _auth_ready (queueing its challenge-proof BANNER
        there would deadlock the handshake), and a pre-registration
        acceptor cannot have app traffic yet (nothing routes to an
        unregistered connection). Those two phases send directly."""
        direct = (self._guarded_dialer_now
                  or (self.inbound and self.peer_name is None))
        if direct:
            sock = self.sock
            if sock is not None:
                sock.sendall(data)   # OSError -> caller tears down
            return
        with self.lock:
            if self.closed:
                return
            self._ctrl_out.append(data)
            self.cond.notify()

    def _writer_loop(self) -> None:
        backoff = 0.01
        while True:
            if self.msgr._stopping:
                return
            with self.lock:
                while not self.out_q and not self._resend \
                        and not self._ctrl_out and not self.closed \
                        and not self.msgr._stopping:
                    self.cond.wait(0.5)
                if self.closed or self.msgr._stopping:
                    # close() is explicit teardown (mark_down/shutdown):
                    # exit NOW, queued or not — draining would mean
                    # re-dialing a peer we were just told to drop, and
                    # a non-empty _resend would otherwise keep this
                    # thread dialing dead peers forever
                    return
                ctrl = b"".join(self._ctrl_out)
                self._ctrl_out.clear()
                # resends (original seq) drain before fresh sends so
                # link_seq stays monotonic on the wire
                resend = self._resend[0] if self._resend else None
                msg = (self.out_q[0]
                       if resend is None and self.out_q else None)
            if self.sock is None:
                # control frames are per-pipe; a dead pipe's are moot
                if msg is None and resend is None:
                    continue
                if not self._connect():
                    time.sleep(backoff)
                    backoff = min(backoff * 2, 1.0)
                    if self.msgr.policy_lossy:
                        with self.lock:
                            self.out_q.clear()
                            self._resend.clear()
                        self.msgr._notify_reset(self.peer_addr)
                    continue
                backoff = 0.01
                # _connect requeued unacked messages AHEAD of the
                # captured head: loop so the oldest sends first (and
                # the pop below always matches what was sent)
                continue
            sock = self.sock
            if sock is None:
                continue  # reader tore it down mid-flight; reconnect
            if ctrl:
                try:
                    sock.sendall(ctrl)
                except OSError:
                    self._on_send_error(sock)
                    continue
            if msg is None and resend is None:
                continue
            if resend is not None:
                seq, msg = resend
            else:
                # fault injection rolls on FRESH sends only — a resend
                # already survived one pipe death; injecting on it too
                # would compound drop probability per reconnect
                if self.msgr._inject_should_drop():
                    with self.lock:
                        if self.out_q and self.out_q[0] is msg:
                            self.out_q.pop(0)
                    continue
                delay = self.msgr._inject_delay()
                if delay:
                    time.sleep(delay)
                if self.sock is None:
                    continue
                self.out_seq += 1
                seq = self.out_seq
            try:
                frame = self._encode_out(msg, seq)
            except Exception:
                # poison message (a field outside the closed encodable
                # set): drop IT, not the writer thread — pickle used to
                # swallow anything, the schema codec does not
                import traceback
                traceback.print_exc()
                with self.lock:
                    if resend is not None:
                        if self._resend and self._resend[0] is resend:
                            self._resend.pop(0)
                    elif self.out_q and self.out_q[0] is msg:
                        self.out_q.pop(0)
                continue
            # bookkeep BEFORE sendall: on a fast loopback the peer's
            # MSGACK for this seq can race the post-send append and
            # trim nothing, redelivering the message on reconnect
            with self.lock:
                self._unacked.append((seq, msg))
            sock = self.sock
            if sock is None:
                # same dual-queue purge as the OSError path: the reader's
                # EOF handler may have already moved the pre-appended
                # entry into _resend while the message also still sits
                # at its queue head — leaving both would send it twice
                with self.lock:
                    self._unacked = [(s, m) for s, m in self._unacked
                                     if s != seq]
                    if resend is None:
                        self._resend = [(s, m) for s, m in self._resend
                                        if s != seq]
                continue
            try:
                sock.sendall(frame)
                with self.lock:
                    if resend is not None:
                        if self._resend and self._resend[0] is resend:
                            self._resend.pop(0)
                    elif self.out_q and self.out_q[0] is msg:
                        self.out_q.pop(0)
            except OSError:
                # purge from BOTH queues: the reader's EOF handler may
                # have moved the in-flight entry into _resend already,
                # and the message is still at its queue head — leaving
                # it in _resend too would send it twice
                with self.lock:
                    self._unacked = [(s, m) for s, m in self._unacked
                                     if s != seq]
                    if resend is None:
                        self._resend = [(s, m) for s, m in self._resend
                                        if s != seq]
                self._on_send_error(sock)
                # lossless: keep msg at head, reconnect and resend

    def _on_send_error(self, sock) -> None:
        try:
            sock.close()
        except OSError:
            pass
        self.sock = None
        self.session_key = None   # next socket re-proves itself
        if self.msgr.policy_lossy:
            with self.lock:
                self.out_q.clear()
                self._unacked.clear()
                self._resend.clear()
            self.msgr._notify_reset(self.peer_addr)

    # -- reader --------------------------------------------------------

    def _reader_loop(self) -> None:
        sock = self.sock
        while not self.closed and sock is not None:
            try:
                hdr = _read_exact(sock, _HDR.size)
                if hdr is None:
                    break
                recv_stamp = time.monotonic()
                magic, length, link_seq, sig = _HDR.unpack(hdr)
                if magic != _MAGIC:
                    break
                payload = _read_exact(sock, length)
                if payload is None:
                    break
            except OSError:
                break
            if not self._verify_frame(payload, link_seq, sig):
                # tampered or unsigned frame on a signing session:
                # FAULT the pipe (reconnect + resend, the reference's
                # check_message_signature fault path) — close() would
                # strand queued lossless traffic
                try:
                    sock.close()
                except OSError:
                    pass
                break
            if not self._process_payload(payload, self._queue_ctrl,
                                         link_seq, recv_stamp):
                break
        if sock is self.sock:
            self.sock = None
            # only the CURRENT pipe's death disarms signing — a stale
            # reader unwinding after a reconnect must not clear the
            # new handshake's key (that would silently disable
            # verification for the fresh session)
            self.session_key = None
        # the pipe died: anything sendall handed to the dying socket
        # is in _unacked with no MSGACK coming. A lossless connection
        # must requeue and reconnect NOW — waiting for the next fresh
        # send would park those messages forever (the reference's
        # Pipe::fault requeues immediately for the same reason).
        if not self.closed and not self.msgr.policy_lossy \
                and (not self.inbound or self._peer_dialable()):
            # (an accepted conn whose peer never advertised a real
            # address has nowhere to re-dial — leave it parked)
            if self.inbound:
                # from here on this conn DIALS the advertised address:
                # it must run the dialer side of the handshake (answer
                # BANNER_RETRY, hold data until mutual auth) or the
                # reconnect could never complete under auth
                self.inbound = False
            with self.lock:
                if self._unacked:
                    self._resend[0:0] = self._unacked
                    self._unacked.clear()
                if self._resend or self.out_q:
                    self.cond.notify_all()

    def _process_payload(self, payload: bytes, send_bytes,
                         link_seq: int = 0,
                         recv_stamp: float = 0.0) -> bool:
        """One inbound frame through the connection protocol (banner
        handshake, restricted pre-auth decode, dispatch). Transport
        agnostic: the threaded reader passes sock.sendall, the async
        engine passes its buffered writer. link_seq is the frame
        header's per-connection sequence (0 = control frame);
        recv_stamp the monotonic time the frame's first bytes were
        read. Returns False to tear the connection down."""
        # pre-auth frames may only materialize closed-set builtins
        # (no registered-struct construction), so an unauthenticated
        # peer cannot reach any type's constructor
        guarded_dialer = self._guarded_dialer_now
        restricted = (
            (self.inbound and self.msgr.auth_verifier is not None
             and self.auth_info is None)
            or guarded_dialer)
        try:
            msg = encoding.decode_any(payload, restricted=restricted)
        except encoding.DecodeError:
            if restricted:
                # a guarded peer sent a non-handshake frame pre-auth
                self.close()
                return False
            return True
        if (isinstance(msg, tuple) and len(msg) in (3, 4, 5, 6)
                and msg[0] == "BANNER"):
            # acceptor side: adopt the peer's advertised listening
            # address and register so sends to it reuse this pipe.
            # With auth enabled, the banner must carry an authorizer
            # whose proof covers our per-connection challenge
            # (BANNER_RETRY round) or the connection drops (EACCES).
            # A 5th element is the dialer's session nonce: the key for
            # exactly-once dedup across reconnects (the reference's
            # in_seq exchange during the connect phase).
            nonce = msg[4] if len(msg) >= 5 else None
            if nonce is not None:
                self._dedup_key = nonce
                self._in_seq = self.msgr._delivered_seq(nonce)
            verifier = self.msgr.auth_verifier
            if verifier is not None:
                authorizer = msg[3] if len(msg) >= 4 else None
                if self._server_challenge is None:
                    self._server_challenge = os.urandom(16)
                if not (isinstance(authorizer, dict)
                        and authorizer.get("has_challenge")):
                    try:
                        send_bytes(_encode(
                            ("BANNER_RETRY", self._server_challenge)))
                    except OSError:
                        return False
                    return True
                try:
                    info = verifier.verify_authorizer(
                        authorizer, challenge=self._server_challenge)
                except Exception:
                    self.close()
                    return False
                self.auth_info = info
                # arm per-message signing with the ticket's session key
                self.session_key = info.get("session_key") \
                    if isinstance(info, dict) else None
                # mutual auth: prove we could read the ticket; the
                # third element tells the dialer our last-delivered
                # in_seq so it can trim already-delivered resends, the
                # fourth is OUR session nonce so the dialer can dedup
                # our messages if this conn later flips to re-dialing
                signing = bool(self.session_key is not None
                               and self.msgr.sign_messages)
                # fail fast on a cephx_sign_messages mismatch: the
                # peers would otherwise churn through reconnects with
                # every frame rejected (the reference gates signing on
                # a negotiated feature bit the same way)
                peer_sign = msg[5] if len(msg) >= 6 else None
                if peer_sign is not None and bool(peer_sign) != signing:
                    self.close()
                    return False
                try:
                    send_bytes(_encode(
                        ("BANNER_ACK", info.get("reply_proof"),
                         self._in_seq, self.conn_nonce, signing)))
                except OSError:
                    return False
            else:
                # no verifier: ack so an auth-capable dialer's
                # handshake wait resolves (its auth_confirm, if any,
                # decides whether a proof-less ack is acceptable)
                try:
                    send_bytes(_encode(("BANNER_ACK", None,
                                        self._in_seq,
                                        self.conn_nonce, False)))
                except OSError:
                    return False
            self.peer_addr = EntityAddr(*msg[1])
            self.peer_name = msg[2]
            self.msgr._register_inbound(self)
            return True
        if (isinstance(msg, tuple) and len(msg) == 2
                and msg[0] == "BANNER_RETRY"):
            # dialer side: the acceptor wants the proof to cover its
            # challenge — re-mint the authorizer and resend the banner
            factory = self.msgr.authorizer_factory
            if self.inbound or factory is None:
                return True
            try:
                authorizer = factory(challenge=msg[1])
            except Exception:
                self.close()
                return False
            self._sent_authorizer = authorizer
            try:
                send_bytes(_encode(
                    ("BANNER", tuple(self.msgr.my_addr or ("", 0)),
                     self.msgr.name, authorizer, self.conn_nonce,
                     self.msgr._sign_intent())))
            except OSError:
                return False
            return True
        if (isinstance(msg, tuple) and len(msg) in (2, 3, 4, 5)
                and msg[0] == "BANNER_ACK"):
            # dialer side: the service proved possession of the
            # session key (cephx mutual auth). The proof bytes are
            # peer-controlled: a confirm that chokes on them is a
            # failed confirmation, not a dead reader thread.
            # A proof-LESS ack (msg[1] is None) means the acceptor
            # runs without a verifier — e.g. the monitor, whose auth
            # is the in-band MAuth protocol, not the banner. The
            # connection then proceeds unauthenticated and unsigned
            # (opportunistic, letting one messenger serve both the
            # authless mon and cephx-guarded OSDs; the reference
            # negotiates auth per service type the same way).
            authless_acceptor = msg[1] is None
            if authless_acceptor and self._sent_authorizer is not None \
                    and tuple(self.peer_addr) not in \
                    self.msgr.authless_peers:
                # downgrade defense: we presented an authorizer and the
                # peer is not a known authless service (monitors are
                # registered in authless_peers by MonClient) — a
                # proof-less ack here is attacker-forgeable (anyone
                # accepting the TCP dial can send one) and would leave
                # the connection unauthenticated AND unsigned while we
                # believe we dialed a cephx-guarded daemon.  Fail the
                # connection instead of proceeding downgraded.
                self.close()
                return False
            confirm = self.msgr.auth_confirm
            if confirm is not None and not authless_acceptor:
                try:
                    ok = confirm(self._sent_authorizer, msg[1])
                except Exception:
                    ok = False
                if not ok:
                    self.close()
                    return False
            # third element: the acceptor's last-delivered in_seq for
            # our session nonce — everything at or below it was already
            # dispatched there, so drop it from the resend sets
            if len(msg) >= 3 and isinstance(msg[2], int) and msg[2] > 0:
                acked = msg[2]
                with self.lock:
                    self._unacked = [(s, m) for s, m in self._unacked
                                     if s > acked]
                    self._resend = [(s, m) for s, m in self._resend
                                    if s > acked]
            # fourth element: the acceptor's session nonce — arms OUR
            # dedup of its messages (so if its conn later flips to
            # re-dialing us, its resends are recognized). REPLACED on
            # every ack: each reconnect lands on a NEW peer conn
            # incarnation with a fresh nonce and restarted seqs, and a
            # stale watermark would falsely drop its messages.
            if len(msg) >= 4 and msg[3]:
                self._dedup_key = msg[3]
                self._in_seq = self.msgr._delivered_seq(msg[3])
            # arm per-message signing: the dialer's copy of the session
            # key comes from its ticket (session_key_fn hook)
            fn = self.msgr.session_key_fn
            if fn is not None and not authless_acceptor:
                try:
                    self.session_key = fn()
                except Exception:
                    self.session_key = None
            # fail fast on a cephx_sign_messages mismatch (see the
            # acceptor-side check): the acceptor's flag rides the ack
            signing = bool(self.session_key is not None
                           and self.msgr.sign_messages)
            peer_sign = bool(msg[4]) if len(msg) >= 5 else None
            if peer_sign is not None and peer_sign != signing:
                self.close()
                return False
            self.auth_confirmed = True
            self._auth_ready.set()
            return True
        # Inbound connections behind a verifier may not deliver
        # anything before a valid banner: a peer that skips the
        # handshake is cut off, not dispatched.
        if (self.inbound and self.msgr.auth_verifier is not None
                and self.auth_info is None):
            self.close()
            return False
        # A guarded dialer ignores inbound traffic until the
        # service has answered the handshake.
        if guarded_dialer:
            return True
        # MSGACK sits BEHIND the auth gates: an unauthenticated peer
        # must not be able to trim the lossless resend set
        if (isinstance(msg, tuple) and len(msg) == 2
                and msg[0] == "MSGACK"):
            # the peer delivered everything up to this link_seq: those
            # messages no longer need resending on reconnect
            with self.lock:
                self._unacked = [(s, m) for s, m in self._unacked
                                 if s > msg[1]]
            return True
        # partition chaos (tests/thrasher.py): a blackholed peer's
        # message FAULTS the pipe — socket down, no MSGACK — so the
        # sender's lossless machinery keeps it in _unacked and
        # redelivers on the post-heal reconnect, exactly like a real
        # network partition healing
        if self.msgr.blocked_peers:
            name = getattr(msg, "from_name", None)
            if name is not None \
                    and tuple(name) in self.msgr.blocked_peers:
                self.close()
                return False
        msg.from_addr = self.peer_addr
        # verified cephx identity of this connection (entity, caps,
        # key_version) rides to dispatchers so daemons enforce caps
        # per op; never encoded (receive-side annotation only)
        msg.auth_info = self.auth_info
        seq = link_seq or None
        msg.link_seq = seq
        if seq is not None and self._dedup_key is not None:
            # ATOMIC admission at the messenger-level watermark: check
            # and record under one lock, BEFORE dispatch. Check-then-
            # record-after-dispatch would leave a window where a stale
            # reader mid-dispatch and the new pipe's reader both pass
            # the check and double-dispatch the same seq. Recording at
            # admission keeps exactly-once; at-least-once holds because
            # the MSGACK still only goes out after the dispatch ran.
            if not self.msgr._admit(self._dedup_key, seq):
                # resend of an already-admitted message (its MSGACK was
                # lost in the reconnect): ack again, do NOT re-deliver
                try:
                    send_bytes(self._encode_out(("MSGACK", seq)))
                except OSError:
                    return False
                return True
            self._in_seq = max(self._in_seq, seq)
        release = self._throttle_admit(msg, len(payload))
        # receive-side stamps (never encoded): the daemon's op span
        # starts at the receipt, and its `ms_recv` child covers the
        # read, decode and throttle wait up to dispatch
        msg.recv_stamp = recv_stamp
        msg.dispatch_stamp = time.monotonic()
        self.msgr._dispatch(msg)
        if release is not None \
                and not getattr(msg, "_throttle_adopted", False):
            # the daemon did not adopt the budget hand-off (early
            # reject, dedup drop, non-op message): release here
            release()
        if seq is not None:
            # ack AFTER dispatch: delivery, not receipt (at-least-once)
            try:
                send_bytes(self._encode_out(("MSGACK", seq)))
            except OSError:
                return False
        return True

    def _throttle_admit(self, msg, cost: int):
        """Blocking dispatch-throttle acquisition for CLIENT messages
        (None when admission control is off or the sender is a
        daemon).  Blocking HERE is the mechanism: while this reader is
        parked, no further frames are read off the socket, the kernel
        buffer fills, and the over-budget client stalls in its own
        sendall (TCP backpressure) instead of growing our op queue.
        Returns an idempotent release closure, also attached as
        msg.throttle_release so the daemon can adopt the budget and
        hold it until the op actually replies."""
        armed = self.msgr.dispatch_throttle
        name = getattr(msg, "from_name", None)
        if armed is None or not name or name[0] != "client":
            return None
        msgs_t, bytes_t, wait_cb = armed
        from ..common.throttle import ThrottleTimeout
        t0 = time.monotonic()
        held_msg = False
        while True:
            if self.closed or self.msgr._stopping:
                # teardown raced the wait: drop the admission, the
                # frame dies with the pipe
                if held_msg:
                    msgs_t.put(1)
                return None
            try:
                if not held_msg:
                    msgs_t.get(1, timeout=0.5)
                    held_msg = True
                bytes_t.get(cost, timeout=0.5)
                break
            except ThrottleTimeout:
                continue   # re-check teardown, keep waiting
        waited = time.monotonic() - t0
        if waited > 0.001 and wait_cb is not None:
            try:
                wait_cb(waited)
            except Exception:
                pass
        done = [False]

        def release():
            if done[0]:
                return
            done[0] = True
            msgs_t.put(1)
            bytes_t.put(cost)

        msg.throttle_release = release
        return release

    def close(self) -> None:
        with self.lock:
            self.closed = True
            self.cond.notify_all()
        sock, self.sock = self.sock, None
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass


class Messenger:
    """Bind + accept + per-peer outgoing connections."""

    def __init__(self, name, nonce: str = "", conf=None,
                 policy_lossy: bool = False,
                 authorizer_factory=None, auth_verifier=None,
                 auth_confirm=None, session_key_fn=None):
        self.name = name              # ("osd", 3) etc.
        self.conf = conf
        self.policy_lossy = policy_lossy
        # cephx connection auth (src/msg AuthAuthorizer plumbing):
        # authorizer_factory(challenge=None) -> dict attached to our
        # outgoing banner (called again with the acceptor's challenge
        # on the BANNER_RETRY round); auth_verifier.verify_authorizer
        # gates inbound banners; auth_confirm(sent_authorizer,
        # reply_proof) -> bool validates the service's mutual-auth
        # BANNER_ACK on dialed connections.
        self.authorizer_factory = authorizer_factory
        self.auth_verifier = auth_verifier
        self.auth_confirm = auth_confirm
        # session_key_fn() -> bytes: the dialer's copy of the cephx
        # session key (from its service ticket), used to sign and
        # verify post-auth frames (cephx_sign_messages); the acceptor's
        # copy comes out of verify_authorizer's info dict.
        self.session_key_fn = session_key_fn
        # peers legitimately allowed to ack our banner WITHOUT a proof
        # (monitors: their auth is the in-band MAuth protocol, not the
        # banner).  MonClient registers the monmap here; a proof-less
        # ack from any OTHER address fails the connection (downgrade
        # defense, see the BANNER_ACK handler).
        self.authless_peers: set = set()
        self.sign_messages = True
        if conf is not None:
            try:
                self.sign_messages = bool(
                    conf.get_val("cephx_sign_messages"))
            except KeyError:
                pass
        self.dispatchers: list[Dispatcher] = []
        self.my_addr: EntityAddr | None = None
        self._server: socket.socket | None = None
        self._accept_thread: threading.Thread | None = None
        self._conns: dict = {}       # peer_addr -> Connection (outgoing)
        self._in_conns: list = []
        # peer session nonce -> last delivered link_seq; survives the
        # per-socket Connection objects so reconnect resends dedup
        # (the reference keeps in_seq on the long-lived Connection that
        # successive Pipes attach to). Bounded: oldest sessions are
        # pruned as new ones register (a pruned-but-live session
        # degrades to at-least-once, never to loss).
        self._delivered: dict = {}
        self._delivered_order: list = []   # nonces, insertion order
        self.DELIVERED_SESSIONS_MAX = 1024
        self._lock = threading.Lock()
        self._stopping = False
        self._rng = random.Random()
        # dispatch-side admission control (osd_client_message_cap /
        # osd_client_message_size_cap, the reference's
        # DispatchQueue throttles): armed by enable_dispatch_throttle
        self.dispatch_throttle = None   # (msgs, bytes, wait_cb)
        # directional blackhole for partition chaos: inbound messages
        # whose from_name is listed here fault the pipe instead of
        # dispatching (tests/thrasher.py partition/heal)
        self.blocked_peers: set = set()

    # -- lifecycle -----------------------------------------------------

    def bind(self, host: str = "127.0.0.1", port: int = 0) -> EntityAddr:
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host, port))
        srv.listen(64)
        srv.settimeout(0.2)
        self._server = srv
        self.my_addr = EntityAddr(host, srv.getsockname()[1])
        return self.my_addr

    def start(self) -> None:
        if self._server is None:
            self.bind()
        self._accept_thread = threading.Thread(target=self._accept_loop,
                                               daemon=True)
        self._accept_thread.start()

    def _accept_loop(self) -> None:
        while not self._stopping:
            try:
                sock, addr = self._server.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn = Connection(self, EntityAddr(*addr), sock=sock)
            conn.start()
            with self._lock:
                self._in_conns.append(conn)

    def _sweep_conns(self) -> None:
        """Close every tracked connection, twice: a dispatch racing the
        first sweep may mint one more connection before _stopping
        lands (shared by both transports' shutdowns)."""
        for _ in range(2):
            with self._lock:
                conns = (list(self._conns.values())
                         + list(self._in_conns))
                self._conns.clear()
                self._in_conns.clear()
            for conn in conns:
                conn.close()

    def _conn_for_send(self, dest_addr, conn_cls):
        """Existing (or freshly minted) connection for dest_addr; None
        once shutdown has begun — a send racing shutdown must not mint
        an untracked connection whose writer re-dials the dead peer's
        port forever (when a later process reuses the port, the zombie
        connects and floods it)."""
        with self._lock:
            if self._stopping:
                return None
            conn = self._conns.get(dest_addr)
            if conn is None or conn.closed:
                conn = conn_cls(self, dest_addr)
                self._conns[dest_addr] = conn
                conn.start()
            return conn

    def shutdown(self) -> None:
        self._stopping = True
        if self._server is not None:
            self._server.close()
        self._sweep_conns()
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=2)

    # -- admission control / partition injection -----------------------

    def enable_dispatch_throttle(self, msg_cap: int, size_cap: int,
                                 wait_cb=None) -> None:
        """Arm dispatch-side admission control: CLIENT messages hold a
        unit of the count budget and their frame bytes of the size
        budget from just-before-dispatch until the daemon replies (or
        dispatch returns, when the daemon doesn't adopt the release).
        An over-budget connection blocks in its reader — the kernel
        socket buffer fills and the client feels TCP backpressure —
        instead of ballooning the op queue.  wait_cb(seconds) observes
        every blocked acquisition (the throttle wait PerfCounter)."""
        from ..common.throttle import Throttle
        self.dispatch_throttle = (
            Throttle("%s-dispatch-msgs" % (self.name,),
                     int(msg_cap or 0)),
            Throttle("%s-dispatch-bytes" % (self.name,),
                     int(size_cap or 0)),
            wait_cb)

    def block_peer(self, name) -> None:
        """Blackhole inbound traffic FROM this entity name (directional
        partition half; the thrasher blocks both directions)."""
        self.blocked_peers.add(tuple(name))

    def unblock_peer(self, name) -> None:
        self.blocked_peers.discard(tuple(name))

    # -- dispatch ------------------------------------------------------

    def add_dispatcher_head(self, d: Dispatcher) -> None:
        self.dispatchers.insert(0, d)

    def add_dispatcher_tail(self, d: Dispatcher) -> None:
        self.dispatchers.append(d)

    def _dispatch(self, msg) -> None:
        for d in self.dispatchers:
            try:
                if d.ms_dispatch(msg):
                    return
            except Exception:
                import traceback
                traceback.print_exc()
                return

    def _register_inbound(self, conn: Connection) -> None:
        """Route future sends to this peer over its inbound connection
        (unless we already dialed them ourselves)."""
        with self._lock:
            existing = self._conns.get(conn.peer_addr)
            if existing is None or existing.closed:
                self._conns[conn.peer_addr] = conn

    def _sign_intent(self) -> bool:
        """The flag a dialer advertises in its BANNER: will our side
        sign post-auth frames? (Effective only when we can actually
        obtain a session key.)"""
        return bool(self.sign_messages
                    and self.session_key_fn is not None)

    def _delivered_seq(self, key) -> int:
        with self._lock:
            return self._delivered.get(key, 0)

    def _admit(self, key, seq: int) -> bool:
        """Atomic dedup admission: True exactly once per (key, seq<=)
        — the watermark check AND advance happen under one lock, so
        two readers (a stale pipe's and the fresh one's) can never
        both win the same seq."""
        with self._lock:
            if key not in self._delivered:
                self._delivered_order.append(key)
                while len(self._delivered_order) > \
                        self.DELIVERED_SESSIONS_MAX:
                    self._delivered.pop(self._delivered_order.pop(0),
                                        None)
            if seq <= self._delivered.get(key, 0):
                return False
            self._delivered[key] = seq
            return True

    def _notify_reset(self, addr) -> None:
        for d in self.dispatchers:
            try:
                d.ms_handle_reset(addr)
            except Exception:
                pass

    # -- send ----------------------------------------------------------

    def send_message(self, msg, dest_addr) -> None:
        if dest_addr is None or self._stopping:
            return
        dest_addr = EntityAddr(*dest_addr)
        msg.from_name = self.name
        conn = self._conn_for_send(dest_addr, Connection)
        if conn is not None:
            conn.send(msg)

    def mark_down(self, dest_addr) -> None:
        """Drop the connection (Messenger::mark_down)."""
        dest_addr = EntityAddr(*dest_addr)
        with self._lock:
            conn = self._conns.pop(dest_addr, None)
        if conn is not None:
            conn.close()

    def mark_down_all(self) -> None:
        with self._lock:
            conns = list(self._conns.values())
            self._conns.clear()
        for conn in conns:
            conn.close()

    # -- fault injection ----------------------------------------------

    def _inject_should_drop(self) -> bool:
        if self.conf is None:
            return False
        n = self.conf.get_val("ms_inject_socket_failures")
        return n > 0 and self._rng.randrange(n) == 0

    def _inject_delay(self) -> float:
        if self.conf is None:
            return 0.0
        mx = self.conf.get_val("ms_inject_delay_max")
        return self._rng.uniform(0, mx) if mx > 0 else 0.0


# Arm the decode registry (message catalog + map/crush structs). At the
# module bottom to break the codecs -> messenger import cycle.
from .. import codecs  # noqa: E402,F401
