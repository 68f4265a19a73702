from . import ops, ref
from .ops import (
    ChunkedForest,
    MatchRegs,
    PackedForest,
    pack_forest,
    pack_forest_chunked,
    propose_device,
    suffix_match_propose,
)
from .ref import suffix_match_propose_ref

__all__ = [
    "ops",
    "ref",
    "ChunkedForest",
    "MatchRegs",
    "PackedForest",
    "pack_forest",
    "pack_forest_chunked",
    "propose_device",
    "suffix_match_propose",
    "suffix_match_propose_ref",
]
