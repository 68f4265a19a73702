"""DAS core: the paper's contribution.

- suffix_tree / suffix_array: nonparametric draft indexes (§4.1)
- drafter: sliding-window, problem-scoped speculators (§4.1.2)
- budget: latency model + optimal speculative budgets (§4.2.1-4.2.2)
- length_policy: Long/Medium/Short runtime classification (§4.2.3)
- verify: lossless speculative verification (greedy + rejection sampling)
- scheduler: continuous-batching slot pool + LPT admission queue
- spec_engine: draft → verify → update rollout loop (`serve`, and the
  batch wrapper `generate`: lock-step by default, slot recycling with
  `slots=n`)
"""

from .budget import (
    AcceptanceModel,
    LatencyModel,
    objective,
    optimal_budgets,
    per_round_budgets,
    residual_tokens,
    solve_budgets,
)
from .drafter import DrafterConfig, DraftSession, PrefixTrie, SuffixDrafter
from .length_policy import (
    CLASS_NAMES,
    LONG,
    MEDIUM,
    SHORT,
    LengthPolicy,
    LengthPolicyConfig,
)
from .scheduler import Request, SlotScheduler
from .suffix_array import SuffixArray
from .suffix_tree import MatchState, SuffixTree

__all__ = [
    "AcceptanceModel",
    "LatencyModel",
    "objective",
    "optimal_budgets",
    "per_round_budgets",
    "residual_tokens",
    "solve_budgets",
    "DrafterConfig",
    "DraftSession",
    "PrefixTrie",
    "SuffixDrafter",
    "CLASS_NAMES",
    "LONG",
    "MEDIUM",
    "SHORT",
    "LengthPolicy",
    "LengthPolicyConfig",
    "Request",
    "SlotScheduler",
    "SuffixArray",
    "MatchState",
    "SuffixTree",
]
