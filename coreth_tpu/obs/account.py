"""One self-time account a thread, and who starved the chip.

An :class:`Account` is a stack of named phases owned by ONE thread.
Every boundary (``enter`` / ``switch`` / ``exit`` / ``tick``) reads the
clock once and charges the time since the last boundary to the phase on
top of the stack, so every instant of the account's life belongs to
exactly one phase — the innermost — and the phases' seconds sum to the
account's age.  It is ALWAYS on: every hot-path site pays one clock
read and a few list updates (no allocation, no contextvar, no ring
event).

CPU seconds are read at MARKS, not at boundaries: ``mark_cpu()`` reads
the owner thread's CPU clock and charges the CPU seconds since the last
mark to the phase on top.  ``time.thread_time()`` is a system call —
5.6 us on the chip's host, where the wall clock costs 0.07 (PERF.md) —
so a boundary cannot carry it.  A site that marks just before both
boundaries of a phase gets that phase's ``cpu_s`` exactly (the recovery
worker's ``sender/native`` a segment, the serve pipeline's
``stream/wait`` a window); the phases between two marks are charged as
ONE, to the phase on top at the second (the execute stage's work of a
window, to ``loop``).  Where the marked phases do not block,
``self_s - cpu_s`` over them is the time the thread stood runnable and
did not run (the GIL, or the machine); in one that blocks it is the
wait.

The threads of a pass keep one each, told apart by ``role``:

- ``replay`` — a ``ReplayEngine`` opens one first thing in its
  constructor.  Its root phase is ``idle`` (engine alive, no call in
  progress; the thread is its caller's, so ``idle`` carries no CPU
  seconds).  A public call of the engine claims the account for its
  thread with ``begin()`` and runs under ``loop``;
- ``recover`` (the engine's recovery worker), ``feed``, ``prefetch``
  (the serve pipeline's threads) — opened by the thread itself with
  :func:`thread_account`, which binds the account to it: root ``idle``
  (thread alive, no job), :func:`current` finds it.

A public call made by ANOTHER thread while an engine's claim stands
(the serve prefetcher's ``warm_senders``) runs on THAT thread's own
account where it has one, and is handed ``NULL`` only where it has
none: no account ever holds a second thread's time.

:class:`InFlight` counts device work dispatched and not yet read back.
One chip runs its queue in order, so seeing ticket *k* finished retires
every ticket <= *k* (a speculative window that is discarded needs no
call of its own).  ``issue`` / ``done`` tick the account first, and at
every boundary the interval just closed is also added to
``starved_s[phase]`` when nothing was in flight over it.  Completion is
seen only when the host reads back, so "in flight" overstates busy and
``starved_s`` is a LOWER bound on the chip's idle time — but over the
whole life of every engine, with no profiler.

When the span tracer is armed (``obs.trace.TRACER``) the same sites
also land in its ring as ``X`` events carrying ``args.id`` /
``args.parent`` (the enclosing phase or span) and open
``jax.profiler.TraceAnnotation("coreth/<phase>")``, which puts the
program's phases into the host plane of any captured profile, on the
device trace's clock.  No phase recurs per block (a catch-up pass over
one-tx blocks would otherwise add 30,000 host events to a profile, and
``stop_trace()`` digests ~35k a second): what does — staging inside
``validate`` — is timed by the engine's own clock pairs and moved over
once a window (:meth:`Account.move`).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

from coreth_tpu.obs import trace as _trace

IDLE = "idle"   # root: the account is alive and no call is in progress
LOOP = "loop"   # root of a public call: what no phase below names


class InFlight:
    """Device work issued and not yet seen finished.  ``DEVICE`` is the
    process's one instance; tests make their own."""

    def __init__(self):
        self._mu = threading.Lock()
        self.issued = 0     # tickets handed out
        self.retired = 0    # high-water mark of tickets seen finished
        self.busy = False   # issued > retired, read bare at boundaries

    @staticmethod
    def _tick(account: Optional["Account"]) -> None:
        """Close the interval before the count changes, on the account
        given or on the one whose public call runs on this thread."""
        acct = account if account is not None else current()
        if acct is not None:
            acct.tick()

    def issue(self, account: Optional["Account"] = None) -> int:
        """Device work has just been dispatched; returns its ticket."""
        self._tick(account)
        with self._mu:
            self.issued += 1
            ticket = self.issued
            self.busy = True
        tr = _trace.TRACER
        if tr is not None:
            tr.instant("device/dispatch", ticket=ticket)
        return ticket

    def done(self, ticket: Optional[int],
             account: Optional["Account"] = None) -> None:
        """The host has read back the work behind ``ticket`` (None: a
        dispatch that never got one — nothing to retire)."""
        if ticket is None:
            return
        self._tick(account)
        with self._mu:
            if ticket > self.retired:
                self.retired = ticket
            self.busy = self.issued > self.retired
        tr = _trace.TRACER
        if tr is not None:
            tr.instant("device/result_fetch", ticket=ticket)

    @property
    def in_flight(self) -> int:
        return self.issued - self.retired


DEVICE = InFlight()

# Accounts opened in this process, newest last: the small Account
# objects only, never their engines (an engine must be free to die).
# The cap holds a benchmark window's accounts of every role (31 passes
# a window at most, up to four accounts a pass).
_ACCOUNTS: deque = deque(maxlen=256)
_ACCOUNTS_MU = threading.Lock()

# the account of this thread: its own (thread_account), or the
# engine's whose public call is in progress on it — what a device seam
# with no engine at hand (evm/device/adapter.py) ticks, and where a
# public call from a thread that is not the engine's lands
_LOCAL = threading.local()

REPLAY = "replay"  # the role of an engine's account


def current() -> Optional["Account"]:
    return getattr(_LOCAL, "account", None)


def thread_account(role: str) -> "Account":
    """A new account of the CALLING thread's own: the thread holds its
    claim from the first instant (``t_open``) and :func:`current`
    returns it there.  For a thread that runs one stage of a pass and
    never claims an engine's account."""
    acct = Account(role)
    acct._owner = threading.get_ident()
    _LOCAL.account = acct
    return acct


def accounts_between(t_lo: float, t_hi: float,
                     role: Optional[str] = REPLAY) -> List["Account"]:
    """The accounts of ``role`` (None: of every role) opened in
    ``[t_lo, t_hi]`` on ``time.monotonic`` (the default clock), oldest
    first.  The default is the engines' own, so that a reader that
    sums a pass's accounts sums one thread's time."""
    with _ACCOUNTS_MU:
        snap = list(_ACCOUNTS)
    return [a for a in snap if t_lo <= a.t_open <= t_hi
            and (role is None or a.role == role)]


device_issue = DEVICE.issue
device_done = DEVICE.done


class Account:
    """Phase stack with self times; see the module docstring."""

    __slots__ = ("role", "thread", "_clock", "_cpu", "_dev", "t_open",
                 "t_last", "_c_last", "_marked", "_recs", "_stack",
                 "_owner", "_mu", "_frames")

    def __init__(self, role: str = REPLAY, clock=time.monotonic,
                 cpu_clock=time.thread_time,
                 device: Optional[InFlight] = None, register: bool = True):
        self.role = role
        # the owner's name: the opener's, then whoever claims it
        self.thread = threading.current_thread().name
        self._clock = clock
        self._cpu = cpu_clock   # the calling thread's, read at marks
        self._c_last: Optional[float] = None   # at the last mark
        self._marked = False    # ever: row() tells unread from 0
        self._dev = DEVICE if device is None else device
        self.t_open = self.t_last = clock()
        # phase name -> [self seconds, starved seconds, entries, CPU
        # seconds]; the stack holds these records, so a boundary costs
        # no dict lookup for the phase it closes
        root = [0.0, 0.0, 1, 0.0]
        self._recs: Dict[str, list] = {IDLE: root}
        self._stack = [root]
        self._owner: Optional[int] = None   # thread inside a public call
        self._mu = threading.Lock()         # guards the claim only
        self._frames: list = []             # open tracer frames (armed)
        if register:
            with _ACCOUNTS_MU:
                _ACCOUNTS.append(self)

    # ------------------------------------------------------- public calls
    def begin(self, root: str = LOOP) -> int:
        """Claim the account for this thread and run under ``root``.
        Returns a token for :meth:`end`: the stack depth to unwind to,
        0 for a call nested in this thread's own, -1 when another
        thread holds the claim — that call is not this account's
        business and its phases go to ``NULL``."""
        me = threading.get_ident()
        with self._mu:
            if self._owner == me:
                return 0
            if self._owner is not None:
                return -1
            self._owner = me
        _LOCAL.account = self
        self.thread = threading.current_thread().name
        self._c_last = None   # the CPU clock is THIS thread's now
        self.enter(root)
        return len(self._stack)

    def end(self, token: int) -> None:
        if token <= 0:
            return
        # an exception may have left phases open below the root
        while len(self._stack) >= token:
            self.exit()
        _LOCAL.account = None
        self._owner = None

    # ------------------------------------------------------------- phases
    def tick(self) -> None:
        """One boundary: the time since the last one goes to the phase
        on top.  Called bare before the in-flight count changes."""
        t = self._clock()
        top = self._stack[-1]
        dt = t - self.t_last
        self.t_last = t
        top[0] += dt
        if not self._dev.busy:
            top[1] += dt

    def mark_cpu(self) -> None:
        """Read the owner thread's CPU clock (a system call: never a
        block) and charge the CPU seconds since the last mark of this
        claim to the phase on top; the first mark only starts the
        count."""
        c = self._cpu()
        if self._c_last is not None:
            self._stack[-1][3] += c - self._c_last
        self._c_last = c
        self._marked = True

    def _push(self, name: str) -> None:
        rec = self._recs.get(name)
        if rec is None:
            rec = self._recs[name] = [0.0, 0.0, 0, 0.0]
        rec[2] += 1
        self._stack.append(rec)
        if _trace.TRACER is not None:
            self._trace_enter(name)

    def enter(self, name: str) -> "Account":
        self.tick()
        self._push(name)
        return self

    def exit(self) -> None:
        self.tick()
        if self._frames:
            self._trace_exit()
        if len(self._stack) > 1:
            self._stack.pop()

    def switch(self, name: str) -> None:
        """Leave the phase on top and enter ``name`` in its place: one
        boundary, one clock read."""
        self.exit()   # exit's boundary, then a push at the same instant
        self._push(name)

    def move(self, src: str, dst: str, seconds: float,
             entries: int = 0) -> None:
        """Reclassify ``seconds`` of ``src``'s self time as ``dst``'s:
        a sub-phase that recurs per block, timed by clock pairs the
        caller already pays and too short to carry boundaries of its
        own.  Its starved part moves in proportion; the sum stays.
        Wall seconds only: the CPU seconds stay with ``src``."""
        self.tick()
        a = self._recs.get(src)
        if a is None or a[0] <= 0.0 or seconds <= 0.0:
            return
        seconds = min(seconds, a[0])
        starved = a[1] * seconds / a[0]
        b = self._recs.get(dst)
        if b is None:
            b = self._recs[dst] = [0.0, 0.0, 0, 0.0]
        a[0] -= seconds
        a[1] -= starved
        b[0] += seconds
        b[1] += starved
        b[2] += entries

    # ``with acct.enter("x"):`` — enter() did the push
    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        self.exit()
        return False

    # ------------------------------------------------- tracer (armed only)
    def _trace_enter(self, name: str) -> None:
        tr = _trace.TRACER
        sid = next(_trace.SPAN_IDS)
        parent = _trace.PARENT.get()
        ann = _trace.annotation("coreth/" + name)
        self._frames.append((len(self._stack), name, sid, parent,
                             _trace.PARENT.set(sid), tr, tr._now_us(),
                             ann))

    def _trace_exit(self) -> None:
        depth, name, sid, parent, tok, tr, t0, ann = self._frames[-1]
        if depth != len(self._stack):
            return  # the phase on top opened before the tracer was armed
        self._frames.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        _trace.PARENT.reset(tok)
        tid = _trace._tid()
        tr._note_thread(tid)
        tr._emit({"ph": "X", "name": name, "ts": t0,
                  "dur": tr._now_us() - t0, "tid": tid,
                  "args": {"id": sid, "parent": parent}})

    # ------------------------------------------------------------ reading
    def row(self) -> dict:
        """The account as of its last boundary (``t_last``): whose it
        is, wall seconds, CPU seconds and entries per phase, and the
        seconds of each phase in which nothing was in flight on the
        device.  ``sum(self_s.values())`` is ``t_last - t_open``;
        ``sum(cpu_s.values())`` is no more: CPU seconds by the phase
        on top at each ``mark_cpu()``, None for an account that never
        marked."""
        recs = dict(self._recs)
        return {"role": self.role, "thread": self.thread,
                "t_open": self.t_open, "t_last": self.t_last,
                "self_s": {k: r[0] for k, r in recs.items()},
                "cpu_s": {k: r[3] for k, r in recs.items()}
                if self._marked else None,
                "n": {k: r[2] for k, r in recs.items()},
                "starved_s": {k: r[1] for k, r in recs.items()}}


class _NullAccount:
    """What a public call made from a thread that is not the
    account's and has none of its own gets, and what a caller with no
    engine behind it (the VM on the host processor) uses in an
    account's place: every phase site is a no-op."""

    __slots__ = ()

    def begin(self, root: str = LOOP) -> int:
        return 0  # claims nothing: end(0) is a no-op on any account

    def end(self, token: int) -> None:
        return None

    def enter(self, name: str) -> "_NullAccount":
        return self

    def switch(self, name: str) -> None:
        return None

    def exit(self) -> None:
        return None

    tick = mark_cpu = exit

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


NULL = _NullAccount()
