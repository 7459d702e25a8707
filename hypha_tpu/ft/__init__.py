"""Fault tolerance for DiLoCo rounds: elastic round membership.

The seed blocks each outer round on exactly ``num_workers`` deltas and
recovers from any worker failure by restarting the whole job. This package
replaces that with graceful degradation — DiLoCo's outer average is well
defined over whichever replicas actually reported:

  detector.py   — φ-accrual failure detector over heartbeats/lease renewals
  membership.py — epoch-numbered RoundMembership + the FT wire vocabulary
  rejoin.py     — catch-up protocol (θ_r = θ₀ + Σ updates) for replacements
  durable.py    — parameter-server round journal + outer-state checkpoint:
                  a PS crash resumes the interrupted round (generation ids
                  + client retry make re-sent deltas idempotent)
  adaptive.py   — WAN-adaptive outer rounds: straggler-adaptive per-worker
                  inner steps (EWMA round-trip history) + per-link codec
                  selection from a measured-bandwidth table
  chaos.py      — deterministic fault injection for tests and their harnesses
                  (kill / delay / partition events + steady degrade modes:
                  slow-CPU workers, per-link bandwidth caps, jitter)

See docs/fault_tolerance.md for the full protocol description.
"""

from .adaptive import Ewma, LinkTable, StragglerController
from .chaos import (
    ChaosAction,
    ChaosController,
    parse_chaos_spec,
    parse_chaos_specs,
)
from .detector import PHI_THRESHOLD_DEFAULT, PhiAccrualDetector
from .durable import GENERATION_KEY, DurablePS, DurableScheduler, RoundJournal
from .membership import (
    PROTOCOL_FT,
    FTConfig,
    MembershipUpdate,
    MembershipView,
    RoundMembership,
    quorum_size,
)
from .rejoin import CATCHUP_KEY, CatchupBuffer, await_catchup

__all__ = [
    "PHI_THRESHOLD_DEFAULT",
    "PhiAccrualDetector",
    "PROTOCOL_FT",
    "FTConfig",
    "MembershipUpdate",
    "MembershipView",
    "RoundMembership",
    "quorum_size",
    "CATCHUP_KEY",
    "GENERATION_KEY",
    "CatchupBuffer",
    "DurablePS",
    "DurableScheduler",
    "RoundJournal",
    "await_catchup",
    "ChaosAction",
    "ChaosController",
    "parse_chaos_spec",
    "parse_chaos_specs",
    "Ewma",
    "LinkTable",
    "StragglerController",
]
