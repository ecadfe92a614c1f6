"""DL002 good: every routing input is a declared, hashed field."""

from dataclasses import dataclass
from typing import Tuple


@dataclass(frozen=True)
class TightPlanSig:
    terms: Tuple[int, ...]
    term_caps: Tuple[int, ...]
    index_joins: Tuple[int, ...] = ()
    planned: bool = False
    n_shards: int = 1

    def describe(self) -> str:           # methods are fine to call
        return f"{len(self.terms)} terms"


def build_tight(sig: TightPlanSig, count_only: bool = False):
    if sig.planned and sig.index_joins:
        return ("index", sig.n_shards, sig.describe())
    if getattr(sig, "planned", False):
        return ("planned", sig.terms)
    return ("greedy", sig.term_caps)


def make(terms, caps):
    return TightPlanSig(terms, caps, planned=True, n_shards=4)
