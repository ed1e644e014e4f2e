"""Signature-keyed executable cache — amortizing t2 across plan churn.

Table 3 of the paper splits the Morpheus cycle into ``t1`` (planning)
and ``t2`` (codegen); ``t2`` dominates.  Keying compiled executables by
the plan's full ``key`` (which includes the TableSet version) means a
control-plane bump or an oscillating hot set (A -> B -> A, the paper's
traffic-dynamics workload) re-pays ``t2`` for code that is behaviorally
identical to an executable already in hand.

:class:`ExecutableCache` fixes that: an LRU map from
``(namespace, plan.signature, batch structure/shapes, donate)`` to the
compiled executable.  The signature carries exactly the trace-time
constants (sites + flags + instrumented — no version), so every plan
that traces to the same jaxpr shares one entry.  One cache instance can
back several consumers:

  * the runtime's *specialized* executable,
  * its *instrumented* twin (``instrumented`` is part of the signature),
  * the non-donating ``run_generic`` oracle (``donate`` is part of the
    key), and
  * — the multi-dataplane seam — several :class:`MorpheusRuntime`\\ s
    passed the same cache instance.  Each runtime gets its own
    ``namespace`` by default; set ``EngineConfig.cache_ns`` to the same
    string on runtimes with identical step functions, table schemas and
    params/batch shapes to actually share executables between them.

The cache is thread-safe, and :meth:`ExecutableCache.get_or_compile`
adds **per-key in-flight deduplication**: when N data planes sharing one
cache (``EngineConfig.cache_ns``) chase the same fleet-wide config push,
exactly one of them runs the compile for each missing key — the others
wait for the owner's insert instead of stampeding XLA with N copies of
the same compilation.  Raw concurrent ``get``/``put`` on the same key
remains last-write-wins (waste, not corruption) for callers that bypass
``get_or_compile``.

The second layer, across processes, is JAX's persistent compilation
cache, which the entry points turn on
(:func:`repro.launch.compile_cache.enable_compile_cache`).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Hashable, Optional

import jax


@dataclass
class CacheStats:
    """Host-side counters of one :class:`ExecutableCache`.
    ``inflight_waits`` counts compile stampedes avoided: callers that
    found another thread/plane already compiling their key and waited
    for its insert instead of compiling again."""
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    inserts: int = 0
    inflight_waits: int = 0
    quarantined: int = 0     # poisoned plan signatures (never recompiled)


def batch_key(batch) -> Hashable:
    """Hashable identity of a batch's *structure*: treedef plus per-leaf
    shape/dtype.  Executables are AOT-compiled against concrete avals,
    so two batches with equal ``batch_key`` run the same executable."""
    leaves, treedef = jax.tree_util.tree_flatten(batch)
    return (treedef,
            tuple((tuple(l.shape), str(l.dtype)) for l in leaves))


class ExecutableCache:
    """Bounded LRU cache of compiled executables.

    Keys are built by the caller (see :meth:`make_key`); values are the
    opaque compiled executables.  ``capacity`` bounds the entry count —
    compiled programs pin device memory, so unbounded growth under plan
    churn is a leak.  Eviction only drops the cache's reference: an
    evicted executable that is still the runtime's active one keeps
    running (the runtime holds its own reference) and is simply
    recompiled on its next miss.
    """

    def __init__(self, capacity: int = 64):
        assert capacity >= 1
        self.capacity = capacity
        self.stats = CacheStats()
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, Any]" = OrderedDict()
        self._inflight: dict = {}       # key -> Event of the compiling
                                        # owner (get_or_compile)
        self._quarantined: set = set()  # poisoned plan signatures

    @staticmethod
    def make_key(ns: Hashable, signature: Hashable, bkey: Hashable,
                 donate: bool = True,
                 fuse: Optional[int] = None) -> Hashable:
        """The cache key anatomy: ``(namespace, plan signature, batch
        structure/shapes, donate)`` — extended with ``("fuse", K)`` for
        ``lax.scan``-fused K-step executables, so a fused window and a
        single step over the same plan never alias (their batch layouts
        and loop structures differ)."""
        if fuse is None:
            return (ns, signature, bkey, donate)
        return (ns, signature, bkey, donate, ("fuse", fuse))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key: Hashable) -> bool:
        with self._lock:
            return key in self._entries

    def get(self, key: Hashable) -> Optional[Any]:
        """The cached executable for ``key`` (marked most-recently-used),
        or None.  Counts a hit or a miss."""
        with self._lock:
            exe = self._entries.get(key)
            if exe is None:
                self.stats.misses += 1
                return None
            self._entries.move_to_end(key)
            self.stats.hits += 1
            return exe

    def probe(self, key: Hashable) -> Optional[Any]:
        """Like :meth:`get` but counting only *hits*: a miss here is
        provisional — callers that route misses through
        :meth:`get_or_compile` use this for the pre-check so the same
        miss is not counted twice."""
        with self._lock:
            exe = self._entries.get(key)
            if exe is not None:
                self._entries.move_to_end(key)
                self.stats.hits += 1
            return exe

    def peek(self, key: Hashable) -> Optional[Any]:
        """Like :meth:`get` but with no stats / recency side effects —
        for introspection and tests."""
        with self._lock:
            return self._entries.get(key)

    def put(self, key: Hashable, exe: Any) -> None:
        """Insert ``exe`` under ``key``, evicting least-recently-used
        entries beyond ``capacity``."""
        with self._lock:
            self._entries[key] = exe
            self._entries.move_to_end(key)
            self.stats.inserts += 1
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.stats.evictions += 1

    def get_or_compile(self, key: Hashable, compile_fn):
        """Fetch ``key``, compiling it with in-flight deduplication on a
        miss: the first caller to miss becomes the *owner* and runs
        ``compile_fn`` (which must return ``(exe, aux)`` — the
        executable plus any caller-side bookkeeping, e.g. the ``t2``
        seconds); every concurrent caller of the same key — another
        thread of this runtime or another data plane sharing the cache —
        waits for the owner's insert instead of compiling the same
        executable again.  Returns ``(exe, aux)`` for the owner and
        ``(exe, None)`` for hits and waiters (aux None = "someone else
        paid t2").  If the owner's compile raises, one waiter claims
        ownership and retries, so a failure never wedges the key."""
        while True:
            with self._lock:
                exe = self._entries.get(key)
                if exe is not None:
                    self._entries.move_to_end(key)
                    self.stats.hits += 1
                    return exe, None
                ev = self._inflight.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._inflight[key] = ev
                    self.stats.misses += 1
                    owner = True
                else:
                    self.stats.inflight_waits += 1
                    owner = False
            if owner:
                try:
                    exe, aux = compile_fn()
                    self.put(key, exe)
                    return exe, aux
                finally:
                    with self._lock:
                        self._inflight.pop(key, None)
                    ev.set()
            ev.wait()

    # ---- quarantine (fleet health) -----------------------------------
    def quarantine(self, signature: Hashable) -> None:
        """Mark a plan *signature* poisoned: the recompile scheduler
        exhausted its bounded retries on a plane whose cycle kept
        failing for this signature.  Recompile cycles consult
        :meth:`is_quarantined` and skip compilation (the plane falls
        through to generic dispatch); every cached executable built
        from the signature is purged so a shared-cache fleet cannot
        keep serving the poisoned code.  Idempotent."""
        with self._lock:
            if signature in self._quarantined:
                return
            self._quarantined.add(signature)
            self.stats.quarantined += 1
            # key anatomy (make_key): key[1] is (plan signature-or-key,
            # instr_struct) — purge every entry compiled from the
            # poisoned signature
            dead = [k for k in self._entries
                    if isinstance(k, tuple) and len(k) >= 2
                    and isinstance(k[1], tuple) and len(k[1]) >= 1
                    and k[1][0] == signature]
            for k in dead:
                del self._entries[k]
                self.stats.evictions += 1

    def unquarantine(self, signature: Hashable) -> None:
        with self._lock:
            if signature in self._quarantined:
                self._quarantined.discard(signature)
                self.stats.quarantined -= 1

    def is_quarantined(self, signature: Hashable) -> bool:
        with self._lock:
            return signature in self._quarantined

    def purge_namespace(self, ns: Hashable) -> int:
        """Drop every entry whose key was built under ``ns`` (counted
        as evictions); returns how many were dropped.  Used when a
        topology epoch ends — a device-loss mesh shrink invalidates
        every executable compiled for the old device set, and the owner
        rotates to a fresh namespace while freeing the dead one."""
        with self._lock:
            dead = [k for k in self._entries
                    if isinstance(k, tuple) and k and k[0] == ns]
            for k in dead:
                del self._entries[k]
                self.stats.evictions += 1
            return len(dead)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

