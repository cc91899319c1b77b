"""Shared static-shape compile cache + bucketing for bounded-compile serving.

Both halves of the system live or die by the same discipline on embedded
hardware: every distinct program *shape* costs an XLA compile, so the hot
path must funnel its dynamic quantities either into traced arguments (the
fed engine's H^k iteration vector) or into a small static ladder of padded
shapes (serving's prefill buckets).  This module holds the two shared
pieces:

``JitCache``
    The per-engine pool of ``jax.jit`` wrappers previously private to
    ``core.fed_engine`` (``_JitCache``).  Entries are keyed by
    ``(entry point name, donated argnums)``; within an entry jax's own
    shape-keyed cache does the ``(H, trainable)``-style static-shape
    keying, and ``num_compiled`` / ``count(name)`` read the true number of
    traced programs back out of it.  Donation variants compile separately
    and are built lazily, so an engine that never donates never pays the
    extra trace.

Bucketing helpers
    ``bucket_for(P) = next_pow2(clamp(P, min_bucket, max_len))`` (capped
    at ``max_len`` so a non-power-of-two cap still bounds the ladder) maps
    a prompt length to the padded prefill length it compiles under;
    ``bucket_ladder`` enumerates the full ladder, whose size — not the
    number of distinct prompt lengths — bounds serving's prefill compile
    count.

See docs/serving.md and docs/fed_engine.md for how each subsystem keys
into the cache.
"""
from __future__ import annotations

import os
import pathlib
import warnings

import jax

from repro import obs

# <checkout>/src/repro/core/compile_cache.py -> <checkout>
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_persistent_cache() -> str:
    """Place JAX's persistent compilation cache; call before the first
    compile. Returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone. Otherwise the cache goes to ``<checkout>/.jax_cache``: a
    fixed path, so every later run from the same checkout finds what an
    earlier one compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class JitCache:
    """Pool of jit wrappers keyed by (entry point, donated argnums).

    Donation variants compile separately, so they are built lazily — an
    engine that never donates never pays the extra trace.  Integer batch
    leaves (LM tokens) can never alias the float outputs; XLA's "donated
    buffers were not usable" note for them is suppressed, it is
    informational and expected.

    Distinct entry points must be distinct callables: jax's executable
    cache (what ``_cache_size`` reads) is shared across jit wrappers of
    the same Python function, so two entries wrapping one function would
    double-count each other's shapes.

    Compile counts prefer jax's own ``_cache_size()`` (the true traced-
    program count, including retraces our key can't see) but that is a
    private jit internal; every ``call`` also records the argument
    shape/dtype signature, so if a jax release drops or renames the
    internal the counts degrade to the recorded-signature count instead
    of raising from every compile-count assertion at once.
    """

    def __init__(self):
        self._jits: dict = {}
        self._seen: dict = {}     # key -> set of arg shape/dtype signatures
        self._spans: dict = {}    # key -> name of the span around a call

    @staticmethod
    def _signature(args) -> tuple:
        return tuple(
            (getattr(leaf, "shape", ()),
             str(getattr(leaf, "dtype", type(leaf).__name__)))
            for leaf in jax.tree_util.tree_leaves(args))

    def call(self, name, fn, donate: tuple, args):
        """Run entry ``name`` on ``args``, inside the span
        ``engine.<name>`` (``<name>[0]`` for a tuple name): the host side
        of argument transfer and enqueue."""
        key = (name, donate)
        if key not in self._jits:
            self._jits[key] = jax.jit(fn, donate_argnums=donate)
            self._seen[key] = set()
            self._spans[key] = "engine." + str(
                name[0] if isinstance(name, tuple) else name)
        with obs.span(self._spans[key]):
            self._seen[key].add(self._signature(args))
            if not donate:
                return self._jits[key](*args)
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                return self._jits[key](*args)

    def _entry_size(self, key) -> int:
        """Traced programs for one (entry point, donate) pool entry, with
        the recorded-signature fallback when the private API is gone."""
        try:
            return int(self._jits[key]._cache_size())
        except Exception:
            return len(self._seen.get(key, ()))

    @property
    def num_compiled(self) -> int:
        """Distinct programs actually traced across every entry point."""
        return sum(self._entry_size(key) for key in self._jits)

    def count(self, name) -> int:
        """Traced programs for one entry point (every shape it compiled
        under, summed over donation variants).  ``name`` matches an entry
        whose key is either ``name`` itself or a tuple starting with it
        (e.g. ``("unstack", n)`` or ``("decode", k_ext)``)."""
        return sum(
            self._entry_size((n, d)) for (n, d) in self._jits
            if n == name or (isinstance(n, tuple) and n and n[0] == name))


# ---------------------------------------------------------------------------
# Prefill-length bucketing
# ---------------------------------------------------------------------------

def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    if n < 1:
        raise ValueError(f"next_pow2 needs n >= 1, got {n}")
    return 1 << (n - 1).bit_length()


def bucket_for(P: int, min_bucket: int, max_len: int) -> int:
    """Padded prefill length for a prompt of length P:
    ``next_pow2(clamp(P, min_bucket, max_len))``, capped at ``max_len``
    (the cache's sequence capacity) when that is not itself a power of
    two.  P must fit the cache: P <= max_len."""
    if P < 1:
        raise ValueError(f"prompt length must be >= 1, got {P}")
    if P > max_len:
        raise ValueError(f"prompt length {P} exceeds max_len {max_len}")
    return min(next_pow2(max(min(P, max_len), min_bucket)), max_len)


def bucket_ladder(min_bucket: int, max_len: int) -> tuple:
    """Every bucket ``bucket_for`` can produce, ascending.  Its length is
    the compile-count bound for bucketed prefill: one program per rung,
    however many distinct prompt lengths arrive."""
    ladder = []
    b = next_pow2(max(1, min_bucket))
    while b < max_len:
        ladder.append(b)
        b *= 2
    ladder.append(max_len)
    return tuple(ladder)
