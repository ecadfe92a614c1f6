"""DL002 bad: routing reads a field the plan signature never declared,
the sig is mutable, and one field opts out of the cache key."""

from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass
class MutablePlanSig:            # not frozen: unhashable-by-value key
    terms: Tuple[int, ...]


@dataclass(frozen=True)
class LeakyPlanSig:
    terms: Tuple[int, ...]
    term_caps: Tuple[int, ...]
    planned: bool = False
    # a routing input excluded from __eq__/__hash__: cache poisoning
    n_shards: int = field(default=1, compare=False)


def build_leaky(sig: LeakyPlanSig, count_only: bool = False):
    if sig.planned and sig.index_joins:  # `index_joins` never declared
        return ("index", sig.terms)
    if getattr(sig, "exch_caps", 0):     # default hides the omission
        return ("exchange", sig.terms)
    return ("greedy", sig.term_caps)


def maybe_build(sig: Optional[LeakyPlanSig]):
    # Optional wrapping must not lose the read check
    return None if sig is None else sig.exch_caps


def make(terms, caps):
    # constructor drift: 4 positional args for 4 fields is fine, but an
    # unknown keyword means the field was deleted out from under a caller
    return LeakyPlanSig(terms, caps, planned=True, index_joins=(1,))


def make_qualified(mod, terms, caps):
    # module-qualified construction gets the same keyword check
    return mod.LeakyPlanSig(terms, caps, exch=4)
