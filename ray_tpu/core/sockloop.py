"""One thread owns each ZeroMQ socket.

A libzmq socket may be used by one thread at a time, and a lock that only
some of its users take orders nothing. So in every process of the control
plane (driver/worker ``Runtime``, ``Controller``, ``NodeManager``) a
:class:`SocketLoop` is the one thread that creates its sockets, polls
them, reads them, writes them and closes them. Any other thread hands it
finished frames with :meth:`SocketLoop.post` and the loop sends them in
FIFO order, or work for that thread with :meth:`SocketLoop.call`;
:class:`Waker` is how such a thread interrupts the loop's poll.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import zmq

from ray_tpu.core import direct as D

logger = logging.getLogger(__name__)

#: a socket the loop owns, and what to do with each message read from it
Handled = Tuple["zmq.Socket", Callable[[List[bytes]], None]]


def open_socket(ctx: "zmq.Context", kind: int,
                identity: Optional[bytes] = None,
                unbounded: bool = True) -> "zmq.Socket":
    """A control-plane socket: no linger, and by default unbounded queues
    (a burst of task results must never be dropped or block at the HWM:
    request/reply traffic has no retransmit)."""
    sock = ctx.socket(kind)
    if identity is not None:
        sock.setsockopt(zmq.IDENTITY, identity)
    sock.setsockopt(zmq.LINGER, 0)
    if unbounded:
        sock.setsockopt(zmq.SNDHWM, 0)
        sock.setsockopt(zmq.RCVHWM, 0)
    return sock


class PeerDealers:
    """Lazily connected DEALERs to peers' direct ROUTERs, for the one
    thread that sends on them. ipc connects never fail, so a DEALER to a
    dead peer would otherwise queue messages forever and the socket itself
    leak; pruning the idle ones bounds both."""

    IDLE_S = 120.0
    PRUNE_EVERY_S = 30.0

    def __init__(self, ctx: "zmq.Context", identity: bytes,
                 session_dir: str):
        self._ctx, self._identity = ctx, identity
        self._session_dir = session_dir
        self._socks: Dict[bytes, list] = {}  # target -> [socket, last used]
        self._pruned_at = time.monotonic()

    def get(self, target: bytes) -> "zmq.Socket":
        now = time.monotonic()
        ent = self._socks.get(target)
        if ent is None:
            sock = open_socket(self._ctx, zmq.DEALER, self._identity)
            sock.connect(D.direct_addr(self._session_dir, target))
            ent = self._socks[target] = [sock, now]
        ent[1] = now
        return ent[0]

    def prune(self) -> None:
        now = time.monotonic()
        if now - self._pruned_at >= self.PRUNE_EVERY_S:
            self._pruned_at = now
            self.close(idle_s=self.IDLE_S)

    def close(self, idle_s: float = -1.0) -> None:
        """Close the sockets idle for longer than ``idle_s``: all, by
        default, which is how their thread ends."""
        now = time.monotonic()
        for target in [t for t, (_, used) in self._socks.items()
                       if now - used > idle_s]:
            self._socks.pop(target)[0].close(0)


class Waker:
    """A pipe a ``zmq.Poller`` can wait on and any thread may write to."""

    def __init__(self):
        self._r, self._w = os.pipe()
        os.set_blocking(self._r, False)
        os.set_blocking(self._w, False)
        # orders wake() against close(): the number of a closed fd is
        # handed out again, and a late wake must not write to its new user
        self._lock = threading.Lock()

    def fileno(self) -> int:
        return self._r

    def wake(self) -> None:
        with self._lock:
            if self._w is None:
                return
            try:
                os.write(self._w, b"\0")
            except BlockingIOError:
                pass  # pipe full: the loop is already due to wake

    def drain(self) -> None:
        """Loop thread only."""
        try:
            while len(os.read(self._r, 4096)) == 4096:
                pass
        except BlockingIOError:
            pass

    def close(self) -> None:
        with self._lock:
            if self._w is not None:
                os.close(self._w)
                os.close(self._r)
                self._w = None

    __del__ = close  # a loop that was never started still returns its fds


class SocketLoop:
    """Poll / drain / dispatch loop of one thread over the sockets it owns.

    ``open_sockets()`` runs on the loop's thread and returns the sockets
    with their handlers; the first is the one :meth:`post` writes to.
    ``each_cycle()`` is the owner's per-cycle work and ``on_close()`` closes
    whatever else the owner opened on this thread; both run there too.
    """

    #: long idle timeout: poll wakes instantly on traffic or a wake;
    #: frequent timer wakeups across many processes starve small hosts
    IDLE_POLL_MS = 1000
    #: messages read from one socket before the outbox and the per-cycle
    #: work get their turn again (poll is level-triggered: the rest waits)
    RECV_BURST = 1000

    def __init__(self, name: str,
                 open_sockets: Callable[[], Sequence[Handled]],
                 each_cycle: Callable[[], None] = lambda: None,
                 on_close: Callable[[], None] = lambda: None):
        self.name = name
        self._open = open_sockets
        self._each_cycle = each_cycle
        self._on_close = on_close
        self._waker = Waker()
        self._outbox: "collections.deque[List[bytes]]" = collections.deque()
        self._calls: "collections.deque[Callable[[], None]]" = \
            collections.deque()
        self._stop = threading.Event()
        self._opened = threading.Event()
        self._open_error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)

    # ------------------------------------------------ any thread may call
    def start(self) -> None:
        """Start the thread; return once its sockets are open (an error
        opening them is raised here)."""
        self._thread.start()
        self._opened.wait()
        if self._open_error is not None:
            raise self._open_error

    def post(self, frames: List[bytes]) -> None:
        """Queue one multipart message for the loop to send."""
        self._outbox.append(frames)
        if not self.on_thread():  # the loop drains before it polls again
            self._waker.wake()

    def call(self, fn: Callable[[], None]) -> None:
        """Run ``fn()`` on the loop's thread, in its next cycle."""
        self._calls.append(fn)
        if not self.on_thread():
            self._waker.wake()

    def on_thread(self) -> bool:
        return threading.current_thread() is self._thread

    def stop(self, wait_s: float = 0.0) -> None:
        """The loop finishes its cycle, sends what was posted before this
        call, closes its sockets and ends; wait up to ``wait_s`` for that
        (not on the loop's own thread)."""
        self._stop.set()
        self._waker.wake()
        if wait_s > 0.0 and self._thread.is_alive() and not self.on_thread():
            self._thread.join(wait_s)

    # ---------------------------------------------------- the loop thread
    def _run(self) -> None:
        try:
            socks = list(self._open())
        except BaseException as e:  # noqa: BLE001 - re-raised by start()
            self._open_error = e
            self._opened.set()
            return
        self._opened.set()
        out = socks[0][0]
        wake_fd = self._waker.fileno()
        poller = zmq.Poller()
        poller.register(wake_fd, zmq.POLLIN)
        for sock, _ in socks:
            poller.register(sock, zmq.POLLIN)
        try:
            while not self._stop.is_set():
                try:
                    events = dict(poller.poll(timeout=self.IDLE_POLL_MS))
                except zmq.ZMQError:
                    break
                if wake_fd in events:
                    self._waker.drain()
                self._drain_outbox(out)
                for sock, handle in socks:
                    if sock in events:
                        self._recv_burst(sock, handle)
                while self._calls:
                    self._guarded(self._calls.popleft(), "a marshaled call")
                self._guarded(self._each_cycle, "per-cycle work")
                self._drain_outbox(out)
        finally:
            self._drain_outbox(out)
            try:
                self._on_close()
            finally:
                for sock, _ in socks:
                    sock.close(0)
                self._waker.close()

    def _guarded(self, fn: Callable[[], None], what: str) -> None:
        try:
            fn()
        except Exception:
            logger.exception("%s: error in %s", self.name, what)

    def _recv_burst(self, sock: "zmq.Socket",
                    handle: Callable[[List[bytes]], None]) -> None:
        for _ in range(self.RECV_BURST):
            try:
                frames = sock.recv_multipart(zmq.NOBLOCK)
            except zmq.ZMQError:
                return
            try:
                handle(frames)
            except Exception:
                logger.exception("%s: error handling %r", self.name,
                                 [f[:16] for f in frames[:2]])

    def _drain_outbox(self, out: "zmq.Socket") -> None:
        while self._outbox:
            frames = self._outbox.popleft()
            try:
                out.send_multipart(frames)
            except zmq.ZMQError as e:
                if not self._stop.is_set():
                    logger.warning("%s: send failed: %s", self.name, e)
