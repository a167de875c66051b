"""Elastic resilience: party-liveness control, degraded-mode WAN sync,
and deterministic fault injection.

The reference *detects* failures (heartbeats -> scheduler dead list,
van.cc:1147-1160; re-admission via ``is_recovery``, van.cc:165-212) but a
dead party still stalls every synchronous round.  This subsystem closes
the loop:

- ``liveness``  — ``PartyLivenessController`` turns heartbeat / roster
  signals into a versioned **membership epoch** (live-party mask +
  renormalization weight) that the sync algorithms and the Trainer
  consume;
- degraded-mode sync lives in ``sync/`` (FSA / MixedSync / PipelinedSync
  accept the mask via ``bind_membership``; the dc-tier aggregate becomes
  a renormalized mean over surviving parties);
- ``chaos``     — seeded, reproducible schedules of party blackouts,
  link flaps and message-drop epochs that drive the controller
  in-process (tests/test_resilience.py).

See docs/resilience.md for the membership/catch-up protocol and the
chaos schedule format.
"""

from geomx_tpu.resilience.chaos import ChaosEngine, ChaosEvent, ChaosSchedule
from geomx_tpu.resilience.liveness import (MembershipEpoch,
                                           PartyLivenessController)

__all__ = ["MembershipEpoch", "PartyLivenessController", "ChaosSchedule",
           "ChaosEvent", "ChaosEngine"]
