"""Event-loop transport: the AsyncMessenger analog.

Role of the reference's async messenger (src/msg/async/
AsyncMessenger.{h,cc}, EventCenter + epoll driver, Protocol V1): a
small fixed pool of event threads multiplexes EVERY connection's I/O
through readiness notifications, instead of two threads per connection.
The split mirrors the reference:

  EventCenter   selectors loop + wakeup pipe + timer heap
                (src/msg/async/Event.cc; EventEpoll driver)
  AsyncConnection  non-blocking state machine: buffered reads feed the
                SAME wire protocol as the threaded transport
                (Connection._process_payload), writes drain from a
                byte buffer on EPOLLOUT-style readiness
  AsyncMessenger   bind/accept/send surface, interchangeable with
                Messenger (conf ms_type = async | simple)

Framing, handshake (cephx challenge rounds), restricted pre-auth
parsing, lossy/lossless policy and fault injection are all shared with
the threaded transport — only the I/O engine differs, exactly the
simple/async split of the reference.
"""

from __future__ import annotations

import errno
import heapq
import os
import selectors
import socket
import threading
import time

from .messenger import (Connection, EntityAddr, Messenger, _encode,
                        _HDR, _MAGIC)

__all__ = ["AsyncMessenger", "EventCenter"]


class EventCenter:
    """One event thread: selectors loop, cross-thread wakeup, timers
    (Event.cc's EventCenter with the epoll driver)."""

    def __init__(self, name: str = "msgr-evt"):
        self.sel = selectors.DefaultSelector()
        self._rwake, self._wwake = os.pipe()
        os.set_blocking(self._rwake, False)
        self.sel.register(self._rwake, selectors.EVENT_READ, self._drain)
        self._timers: list = []      # heap of (due, seq, fn)
        self._seq = 0
        self._pending: list = []     # cross-thread callbacks
        self._lock = threading.Lock()
        self._stopping = False
        self.thread = threading.Thread(target=self._loop, name=name,
                                       daemon=True)

    def start(self) -> None:
        self.thread.start()

    def stop(self) -> None:
        self._stopping = True
        self.wakeup()
        self.thread.join(timeout=2)
        if self.thread.is_alive():
            # a stuck callback still owns the selector: closing it now
            # would turn the loop into a 100%-CPU spin on OSError.
            # Leak the fds; the loop exits at its next top-of-loop
            # _stopping check (or with the process).
            return
        try:
            self.sel.close()
        except Exception:
            pass
        for fd in (self._rwake, self._wwake):
            try:
                os.close(fd)
            except OSError:
                pass

    def wakeup(self) -> None:
        try:
            os.write(self._wwake, b"x")
        except OSError:
            pass

    def _drain(self, _mask) -> None:
        try:
            while os.read(self._rwake, 4096):
                pass
        except (BlockingIOError, OSError):
            pass

    def call_soon(self, fn) -> None:
        """Run fn on the event thread (thread-safe)."""
        with self._lock:
            self._pending.append(fn)
        self.wakeup()

    def call_later(self, delay: float, fn) -> None:
        with self._lock:
            self._seq += 1
            heapq.heappush(self._timers,
                           (time.monotonic() + delay, self._seq, fn))
        self.wakeup()

    def _loop(self) -> None:
        while True:
            with self._lock:
                pending, self._pending = self._pending, []
                now = time.monotonic()
                due = []
                while self._timers and self._timers[0][0] <= now:
                    due.append(heapq.heappop(self._timers)[2])
                timeout = (max(0.0, self._timers[0][0] - now)
                           if self._timers else 0.5)
            for fn in pending + due:
                try:
                    fn()
                except Exception:
                    import traceback
                    traceback.print_exc()
            if self._stopping:
                # drain-then-exit: close callbacks scheduled by
                # shutdown() must still run or their sockets leak
                with self._lock:
                    leftover, self._pending = self._pending, []
                for fn in leftover:
                    try:
                        fn()
                    except Exception:
                        pass
                return
            try:
                events = self.sel.select(timeout)
            except OSError:
                if self._stopping:
                    return   # selector closed under us during shutdown
                continue
            for key, mask in events:
                try:
                    key.data(mask)
                except Exception:
                    import traceback
                    traceback.print_exc()


class AsyncConnection(Connection):
    """Connection over the event loop: no per-connection threads.

    Reuses the base class's protocol (_process_payload) and state;
    replaces the reader/writer threads with buffered non-blocking I/O
    driven by EventCenter readiness callbacks."""

    def __init__(self, msgr: "AsyncMessenger", peer_addr, sock=None):
        super().__init__(msgr, peer_addr, sock=sock)
        self.center = msgr.center
        self._inbuf = bytearray()
        self._rx_stamp = 0.0         # first bytes of the head frame read
        # protocol/handshake bytes (regenerated per connection) flush
        # ahead of data; exactly ONE message frame is in flight at a
        # time and its message stays at the head of out_q until fully
        # sent — the lossless resend contract (threaded writer pops
        # only after sendall succeeds; this is the async equivalent)
        self._ctrl = bytearray()
        self._cur = bytearray()      # the in-flight frame's bytes
        self._cur_msg = None
        self._cur_seq = 0
        self._cur_from_resend = False
        self._blocked_until = 0.0    # fault-injected delay gate
        self._delay_paid = False     # head message already rolled
        self._connecting = False
        self._registered = False
        if sock is not None:
            sock.setblocking(False)

    # -- base-class seams we do NOT want -------------------------------

    def start(self) -> None:                 # no threads
        if self.sock is not None:
            self.center.call_soon(self._register_io)

    def _start_reader(self) -> None:         # no reader thread
        pass

    # -- send (any thread) ---------------------------------------------

    def send(self, msg) -> None:
        with self.lock:
            if self.closed:
                return
            self.out_q.append(msg)
        self.center.call_soon(self._pump)

    # -- event-thread internals ----------------------------------------

    def _events(self) -> int:
        ev = selectors.EVENT_READ
        if self._ctrl or self._cur or self._connecting:
            ev |= selectors.EVENT_WRITE
        return ev

    def _register_io(self) -> None:
        if self.closed or self.sock is None or self._registered:
            return
        try:
            self.sel_key = self.center.sel.register(
                self.sock, self._events(), self._on_io)
            self._registered = True
        except KeyError:
            # fd-number reuse: a socket closed behind our back (epoll
            # silently drops closed fds, so an idle connection never
            # gets an event to tear itself down) left a stale selectors
            # entry under this fd. The kernel only re-issues an fd
            # number after the old one closed, so the stale entry is
            # provably dead — evict it and retry.
            if self._evict_stale_fd():
                try:
                    self.sel_key = self.center.sel.register(
                        self.sock, self._events(), self._on_io)
                    self._registered = True
                except (KeyError, ValueError, OSError):
                    pass
        except (ValueError, OSError):
            pass

    def _evict_stale_fd(self) -> bool:
        try:
            fd = self.sock.fileno()
        except (OSError, ValueError):
            return False
        try:
            stale = self.center.sel.get_map().get(fd)
        except (KeyError, RuntimeError):
            stale = None
        if stale is None:
            return False
        try:
            self.center.sel.unregister(stale.fileobj)
        except (KeyError, ValueError, OSError):
            return False
        owner = getattr(stale.data, "__self__", None)
        if owner is not None and owner is not self:
            owner._registered = False
        return True

    def _reregister(self) -> None:
        if self._registered and self.sock is not None:
            try:
                self.center.sel.modify(self.sock, self._events(),
                                       self._on_io)
            except (KeyError, ValueError, OSError):
                pass

    def _unregister(self) -> None:
        if self._registered and self.sock is not None:
            try:
                self.center.sel.unregister(self.sock)
            except (KeyError, ValueError, OSError):
                pass
        self._registered = False

    def _buffer_bytes(self, data: bytes) -> None:
        """The protocol's send_bytes: control-plane bytes, buffered
        ahead of data frames, never blocks."""
        self._ctrl += data
        self._reregister()

    def _pump(self) -> None:
        """Move the head of out_q toward the wire (event thread).
        One frame in flight; the message pops only once fully sent."""
        if self.closed:
            return
        if self.sock is None:
            if not self._connecting:
                self._start_connect()
            return
        if self._guarded_dialer_now or self._connecting:
            return                   # frames held until mutual auth
        now = time.monotonic()
        if now < self._blocked_until:
            self.center.call_later(self._blocked_until - now,
                                   self._pump)
            return
        while not self._cur:
            with self.lock:
                resend = self._resend[0] if self._resend else None
            if resend is not None:
                # reconnect resend: original link_seq on the wire so
                # the peer's dedup can identify it (exactly-once)
                seq, msg = resend
                try:
                    frame = self._encode_out(msg, seq)
                except Exception:
                    import traceback
                    traceback.print_exc()
                    with self.lock:
                        if self._resend and self._resend[0] is resend:
                            self._resend.pop(0)
                    continue
                self._cur = bytearray(frame)
                self._cur_msg = msg
                self._cur_seq = seq
                self._cur_from_resend = True
                break
            with self.lock:
                if not self.out_q:
                    break
                msg = self.out_q[0]
            # fault injection rolls ONCE per message (a paid delay must
            # not re-roll on the post-delay re-entry, or a nonzero
            # delay_max blocks the stream forever)
            if not self._delay_paid:
                if self.msgr._inject_should_drop():
                    with self.lock:
                        if self.out_q and self.out_q[0] is msg:
                            self.out_q.pop(0)
                    continue
                delay = self.msgr._inject_delay()
                if delay:
                    # gate the whole STREAM, not just this frame —
                    # per-frame deferral would reorder the connection
                    self._delay_paid = True
                    self._blocked_until = time.monotonic() + delay
                    self.center.call_later(delay, self._pump)
                    return
            self._delay_paid = False
            self.out_seq += 1
            try:
                frame = self._encode_out(msg, self.out_seq)
            except Exception:
                import traceback
                traceback.print_exc()
                with self.lock:
                    if self.out_q and self.out_q[0] is msg:
                        self.out_q.pop(0)
                continue
            self._cur = bytearray(frame)
            self._cur_msg = msg
            self._cur_seq = self.out_seq
            self._cur_from_resend = False
        self._flush()

    def _start_connect(self) -> None:
        authorizer = None
        if self.msgr.authorizer_factory is not None:
            try:
                authorizer = self.msgr.authorizer_factory()
            except Exception:
                self._schedule_reconnect()
                return
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setblocking(False)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        err = sock.connect_ex(tuple(self.peer_addr))
        if err not in (0, errno.EINPROGRESS, errno.EWOULDBLOCK):
            try:
                sock.close()
            except OSError:
                pass
            self._schedule_reconnect()
            return
        self.auth_confirmed = False
        self._auth_ready.clear()
        self.session_key = None
        self._sent_authorizer = authorizer
        self.sock = sock
        self._connecting = True
        self._ctrl = bytearray(_encode(
            ("BANNER", tuple(self.msgr.my_addr or ("", 0)),
             self.msgr.name, authorizer, self.conn_nonce,
             self.msgr._sign_intent()))) + self._ctrl
        self._register_io()

    def _schedule_reconnect(self) -> None:
        if self.closed or self.msgr._stopping:
            return
        if self.msgr.policy_lossy:
            with self.lock:
                self.out_q.clear()
                self._unacked.clear()
                self._resend.clear()
            self._delay_paid = False
            self.msgr._notify_reset(self.peer_addr)
            return
        self.center.call_later(0.2, self._pump)

    def _teardown(self) -> None:
        """Connection-level failure on the event thread. The in-flight
        message stays at the head of out_q (its frame is re-encoded and
        resent whole after reconnect — at-least-once, exactly like the
        threaded writer's keep-at-head semantics)."""
        self._unregister()
        sock, self.sock = self.sock, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        self.session_key = None   # next socket re-proves itself
        self._inbuf = bytearray()
        self._ctrl = bytearray()
        self._cur = bytearray()
        self._cur_msg = None
        self._cur_from_resend = False
        self._connecting = False
        self._delay_paid = False     # the paid head no longer exists
        if self.closed:
            return
        if self.inbound:
            # an accepted connection with pending lossless traffic and
            # a known peer address flips to dialer mode — dying with
            # _unacked messages would strand them (the threaded
            # transport's inbound conns re-dial the same way)
            with self.lock:
                pending = bool(self._unacked or self.out_q
                               or self._resend)
                if pending and not self.msgr.policy_lossy \
                        and self._peer_dialable():
                    self.inbound = False
                    self._resend[0:0] = self._unacked
                    self._unacked.clear()
                else:
                    self.closed = True
                    return
        self._schedule_reconnect()   # lossless dialers reconnect

    def _on_io(self, mask) -> None:
        if self.closed:
            self._unregister()
            return
        if mask & selectors.EVENT_WRITE:
            if self._connecting:
                err = self.sock.getsockopt(socket.SOL_SOCKET,
                                           socket.SO_ERROR)
                if err:
                    self._teardown()
                    return
                self._connecting = False
                if not (self.msgr.auth_confirm is not None
                        or self.msgr.authorizer_factory is not None):
                    self.auth_confirmed = True
                # fresh pipe: unacked messages resend first, keeping
                # their original link_seq for the peer's dedup
                with self.lock:
                    if self._unacked:
                        self._resend[0:0] = self._unacked
                        self._unacked.clear()
                self._pump()
            self._flush()
        if mask & selectors.EVENT_READ:
            self._on_readable()

    def _flush(self) -> None:
        if self.sock is None or self._connecting:
            return
        progressed = True
        while progressed and (self._ctrl or self._cur):
            progressed = False
            buf = self._ctrl if self._ctrl else self._cur
            try:
                n = self.sock.send(buf)
            except (BlockingIOError, InterruptedError):
                break
            except OSError:
                self._teardown()
                return
            if n > 0:
                del buf[:n]          # in-place, no re-allocation
                progressed = True
            if not self._cur and self._cur_msg is not None:
                # frame fully on the wire: the message leaves the queue
                # but stays in _unacked until the peer's MSGACK — bytes
                # accepted by a dying TCP buffer are not delivery
                with self.lock:
                    if self._cur_from_resend:
                        if (self._resend
                                and self._resend[0][1] is self._cur_msg):
                            self._resend.pop(0)
                    elif self.out_q and self.out_q[0] is self._cur_msg:
                        self.out_q.pop(0)
                    self._unacked.append((self._cur_seq, self._cur_msg))
                self._cur_msg = None
                self._cur_from_resend = False
                self.center.call_soon(self._pump)
        self._reregister()

    def _on_readable(self) -> None:
        sock = self.sock
        if sock is None:
            return
        # when the first bytes of the frame at the buffer's head were
        # read: this event's read, unless a partial frame waited
        now = time.monotonic()
        head_stamp = self._rx_stamp if self._inbuf else now
        try:
            while True:
                chunk = sock.recv(65536)
                if chunk == b"":
                    self._teardown()
                    return
                self._inbuf += chunk
                if len(chunk) < 65536:
                    break
        except (BlockingIOError, InterruptedError):
            pass
        except OSError:
            self._teardown()
            return
        off = 0
        buf = self._inbuf
        try:
            while len(buf) - off >= _HDR.size:
                magic, length, link_seq, sig = _HDR.unpack_from(buf, off)
                if magic != _MAGIC:
                    self._teardown()
                    return
                if len(buf) - off < _HDR.size + length:
                    break
                payload = bytes(buf[off + _HDR.size:
                                    off + _HDR.size + length])
                off += _HDR.size + length
                if not self._verify_frame(payload, link_seq, sig):
                    # tampered/unsigned frame on a signing session
                    self._teardown()
                    return
                was_confirmed = self.auth_confirmed
                if not self._process_payload(payload,
                                             self._buffer_bytes,
                                             link_seq, head_stamp):
                    self._teardown()
                    return
                head_stamp = now
                if self.auth_confirmed and not was_confirmed:
                    self._pump()     # auth landed: release held frames
        finally:
            if off and buf is self._inbuf:
                del self._inbuf[:off]   # one compaction per event
            self._rx_stamp = head_stamp

    def close(self) -> None:
        with self.lock:
            self.closed = True
        def _do():
            self._unregister()
            sock, self.sock = self.sock, None
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
        self.center.call_soon(_do)


class AsyncMessenger(Messenger):
    """Messenger over one EventCenter (conf ms_type=async).

    Same surface and policies as the threaded Messenger; connections
    are AsyncConnections sharing the event thread."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.center = EventCenter("msgr-evt-%s" % (self.name,))
        self._started = False

    def start(self) -> None:
        if self._server is None:
            self.bind()
        self._server.settimeout(0)   # non-blocking accept
        self.center.start()
        self._started = True
        self.center.call_soon(self._register_accept)

    def _register_accept(self) -> None:
        try:
            self.center.sel.register(self._server,
                                     selectors.EVENT_READ,
                                     self._on_accept)
        except (KeyError, ValueError, OSError):
            pass

    def _on_accept(self, _mask) -> None:
        while True:
            try:
                sock, addr = self._server.accept()
            except (BlockingIOError, socket.timeout):
                return
            except OSError:
                return
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn = AsyncConnection(self, EntityAddr(*addr), sock=sock)
            with self._lock:
                self._in_conns.append(conn)
            conn._register_io()
            # an accepted dialer-less peer needs no banner from us;
            # auth acks ride _process_payload

    def send_message(self, msg, dest_addr) -> None:
        if dest_addr is None or self._stopping:
            return
        dest_addr = EntityAddr(*dest_addr)
        msg.from_name = self.name
        conn = self._conn_for_send(dest_addr, AsyncConnection)
        if conn is not None:
            conn.send(msg)

    def shutdown(self) -> None:
        self._stopping = True
        self._sweep_conns()
        if self._started:
            self.center.stop()
        if self._server is not None:
            try:
                self._server.close()
            except OSError:
                pass


def create_messenger(name, conf=None, **kwargs):
    """Transport factory (ms_type): 'simple' = threaded (default),
    'async' = event-loop."""
    ms_type = "simple"
    if conf is not None:
        try:
            ms_type = conf.get_val("ms_type")
        except KeyError:
            ms_type = "simple"
    cls = AsyncMessenger if ms_type == "async" else Messenger
    return cls(name, conf=conf, **kwargs)
