"""Runtime enforcement of the statically-linted invariants (debug_guards).

tpulint proves the *source* clean; this module catches what static analysis
cannot see — dynamically-dispatched host syncs and lock acquisitions — by
arming two guards when the ``debug_guards`` flag is "log" or "disallow":

- ``hot_path_guard()`` wraps compiled-plan execution in a
  ``jax.transfer_guard_device_to_host`` scope: any implicit device->host
  transfer inside the hot path (a stray ``int(x)`` / ``np.asarray``) logs or
  raises instead of silently stalling the pipeline.  Host->device constant
  uploads stay allowed — they are part of tracing.
- ``GuardedLock`` is a drop-in threading.Lock/RLock whose acquisitions
  assert the statically-derived lock ORDER (tools/tpulint.py --lock-order):
  every lock carries a rank, and acquiring a lower/equal rank while holding
  a higher one is an inversion — the dynamic half of LOCKORDER.
  tests/test_lint.py cross-checks the declared ranks against the static
  acquisition graph, so the two layers cannot drift apart.
- the **lockset witness** is the dynamic half of GUARDEDBY
  (analysis/ownership.py): classes call ``register_witness`` with their
  statically-inferred ``{attr: lock}`` ownership, and arming the flag
  installs ``_OwnedAttr`` data descriptors that assert every access to an
  owned attribute happens while the owning ``GuardedLock`` is held by the
  accessing thread — the static model checked against real interleavings
  by the stress/chaos suites.

Trips surface in ``metrics`` (``guard_transfer_trips`` /
``guard_lock_trips`` / ``guard_owner_trips``) and on the EXPLAIN ANALYZE
``-- guards:`` line.

CPU caveat: on the CPU backend device->host reads are zero-copy views, so
jax's transfer guard never fires there — the transfer half of debug_guards
is a no-op on the CPU backend and bites on real accelerators, which is
exactly where the sync costs a round-trip.  The lock half is
backend-independent.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

from ..utils import metrics
from ..utils.flags import FLAGS, define

define("debug_guards", "off",
       "runtime trace/transfer/lock guards on the hot path: off | log "
       "(transfers logged by jax to stderr, lock trips counted) | disallow "
       "(fail the query/acquisition; trips counted) — the dynamic half of "
       "tools/tpulint.py")

guard_transfer_trips = metrics.Counter("guard_transfer_trips")
guard_lock_trips = metrics.Counter("guard_lock_trips")
guard_owner_trips = metrics.Counter("guard_owner_trips")

# the flag is re-read on every lock acquisition of the hottest paths:
# cache the resolved mode and refresh through the flag listener instead
_MODE = "off"


def _refresh_mode(value=None) -> None:
    global _MODE
    mode = str(FLAGS.debug_guards if value is None else value).lower()
    _MODE = mode if mode in ("log", "disallow") else "off"
    _arm_witnesses(_MODE != "off")


# -- lockset witness (dynamic GUARDEDBY) ---------------------------------

# registered classes: cls -> (static_id, {attr: lock_attr}); descriptors
# are installed/removed as the flag flips so production classes stay
# plain-attribute fast when guards are off
_WITNESSES: dict = {}
_ARMED = False


class _OwnedAttr:
    """Data descriptor asserting accesses to a lock-owned instance
    attribute happen while the owning lock is held BY THIS THREAD.  The
    value itself lives in the instance ``__dict__`` (the descriptor wins
    the lookup because it is a data descriptor); the first ``__set__``
    (construction, before the object is published) is exempt."""

    def __init__(self, name: str, lock_attr: str, static_id: str):
        self.name = name
        self.lock_attr = lock_attr
        self.static_id = static_id

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        try:
            val = obj.__dict__[self.name]
        except KeyError:
            raise AttributeError(self.name) from None
        self._check(obj, "read")
        return val

    def __set__(self, obj, value):
        if self.name in obj.__dict__:      # first set = construction
            self._check(obj, "write")
        obj.__dict__[self.name] = value

    def __delete__(self, obj):
        self._check(obj, "delete")
        del obj.__dict__[self.name]

    def _check(self, obj, verb: str) -> None:
        if _MODE == "off":      # descriptors may outlive a flag flip
            return
        lk = getattr(obj, self.lock_attr, None)
        if lk is None:
            return
        if isinstance(lk, GuardedLock):
            held = lk.held_by_me()
        else:                   # plain lock: best effort (any holder)
            held = bool(getattr(lk, "locked", lambda: True)())
        if held:
            return
        guard_owner_trips.add(1)
        msg = (f"lockset witness: {verb} of {self.static_id}.{self.name} "
               f"without holding self.{self.lock_attr} (statically "
               "inferred owner — analysis/ownership.py)")
        if _MODE == "disallow":
            raise RuntimeError(msg)
        import sys
        print(f"tpulint-guard: {msg}", file=sys.stderr)


def register_witness(cls, static_id: str,
                     attrs: dict | None = None) -> None:
    """Enroll ``cls`` in the lockset witness.  ``attrs`` ({attr:
    lock_attr}) defaults to the static pass's inferred ownership for
    ``static_id`` (``analysis.ownership.package_ownership()``), resolved
    lazily at ARM time so import-time registration costs nothing.
    Installs immediately if guards are already armed."""
    if getattr(cls, "__slots__", None) is not None:
        return                  # no instance __dict__ to host the values
    _WITNESSES[cls] = (static_id, attrs)
    if _ARMED:
        _install_witness(cls, static_id, attrs)


def _resolve_attrs(static_id: str, attrs: dict | None) -> dict:
    if attrs is not None:
        return attrs
    from .ownership import package_ownership
    return dict(package_ownership().get(static_id, {}))


def _install_witness(cls, static_id, attrs) -> None:
    for attr, lock_attr in _resolve_attrs(static_id, attrs).items():
        if not isinstance(cls.__dict__.get(attr), _OwnedAttr):
            setattr(cls, attr, _OwnedAttr(attr, lock_attr, static_id))


def _arm_witnesses(on: bool) -> None:
    global _ARMED
    if on == _ARMED:
        return
    _ARMED = on
    for cls, (static_id, attrs) in _WITNESSES.items():
        if on:
            _install_witness(cls, static_id, attrs)
        else:
            for attr, cur in list(cls.__dict__.items()):
                if isinstance(cur, _OwnedAttr):
                    delattr(cls, attr)


def witness_stats() -> dict:
    """Introspection: armed state + per-class witnessed attrs (resolved
    view — triggers the static parse when defaults are in play)."""
    return {"armed": _ARMED,
            "classes": {sid: sorted(_resolve_attrs(sid, attrs))
                        for sid, attrs in _WITNESSES.values()}}


_refresh_mode()
FLAGS.on_change("debug_guards", _refresh_mode)


def guard_mode() -> str:
    return _MODE


@contextmanager
def hot_path_guard():
    """Execution scope for compiled query programs: no implicit
    device->host transfer may happen inside.  Egress/flag reads belong
    AFTER this scope, spelled ``jax.device_get``."""
    mode = guard_mode()
    if mode == "off":
        yield
        return
    import jax

    # log mode defers to jax's own stderr logging (the C++ guard offers no
    # python hook to count), so guard_transfer_trips only moves in
    # disallow mode — where the failed query makes the trip loud anyway
    try:
        with jax.transfer_guard_device_to_host(
                "log" if mode == "log" else "disallow"):
            yield
    except Exception as e:
        if "transfer" in str(e).lower():
            guard_transfer_trips.add(1)
        raise


# declared lock ranks, validated against the static graph by
# tests/test_lint.py (every static edge A->B must have rank[A] < rank[B])
LOCK_RANKS: dict[str, int] = {}


class GuardedLock:
    """threading.Lock/RLock + rank-ordered acquisition assertion.

    With debug_guards off, acquire() is one module-global read plus the
    underlying C lock — no stack bookkeeping, no flag parse.  Arming the
    flag mid-hold therefore starts with an empty view of already-held
    locks (checks engage on the next full acquisition chain); that
    best-effort window is the price of a zero-cost production path."""

    _tls = threading.local()

    def __init__(self, name: str, rank: int, reentrant: bool = False):
        self._lk = threading.RLock() if reentrant else threading.Lock()
        self.name = name
        self.rank = rank
        LOCK_RANKS[name] = rank

    @classmethod
    def _stack(cls) -> list:
        st = getattr(cls._tls, "stack", None)
        if st is None:
            st = cls._tls.stack = []
        return st

    def _check_order(self) -> None:
        st = self._stack()
        # re-entering a lock this thread ALREADY holds is always safe
        # (RLock semantics) even if higher-rank locks were taken since
        if self in st:
            return
        # strict >: same-rank locks (two tables' store locks) may nest
        # freely — give locks DISTINCT ranks when their order matters
        if st and st[-1].rank > self.rank:
            guard_lock_trips.add(1)
            msg = (f"lock order violation: acquiring {self.name} "
                   f"(rank {self.rank}) while holding {st[-1].name} "
                   f"(rank {st[-1].rank}) — the static order "
                   "(tools/tpulint.py --lock-order) forbids this nesting")
            if _MODE == "disallow":
                raise RuntimeError(msg)
            import sys
            print(f"tpulint-guard: {msg}", file=sys.stderr)

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if _MODE == "off":      # production fast path: no bookkeeping
            return self._lk.acquire(blocking, timeout)
        self._check_order()
        ok = self._lk.acquire(blocking, timeout)
        if ok:
            self._stack().append(self)
        return ok

    def release(self) -> None:
        if _MODE != "off":
            st = self._stack()
            if st and st[-1] is self:
                st.pop()
            elif self in st:    # out-of-order release: still unwind
                st.remove(self)
        elif getattr(self._tls, "stack", None):
            # flag flipped off mid-hold: drain stale entries lazily
            st = self._tls.stack
            if self in st:
                st.remove(self)
        self._lk.release()

    def __enter__(self) -> "GuardedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False

    def locked(self) -> bool:
        lk = self._lk
        return lk.locked() if hasattr(lk, "locked") else False

    def held_by_me(self) -> bool:
        """Whether THIS thread is inside the lock.  Stack-based, so only
        meaningful while debug_guards is armed (acquisitions made with
        guards off were never pushed — the same best-effort window as
        the order check, see the class docstring)."""
        return self in self._stack()


def guard_stats() -> dict:
    """The EXPLAIN ANALYZE / SHOW METRICS payload."""
    return {"mode": guard_mode(),
            "transfer_trips": guard_transfer_trips.value,
            "lock_trips": guard_lock_trips.value,
            "owner_trips": guard_owner_trips.value}
