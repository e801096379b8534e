"""Fleet data flywheel: served traffic becomes the training stream.

ISSUE 18 — the serve→collect→train→redeploy cycle QT-Opt actually ran
(PAPER.md): the serving fleet's answered requests are captured at the
dispatch seam, closed against the env-dynamics oracle, validated
against the replay spec, and re-ingested as the learner's data — whose
exports then flow back through shadow→canary→promote to change the
very traffic they will later train on.

Layout:
  capture.py        EpisodeRecorder (the PolicyReplica._flush seam),
                    FlywheelIngest (the spec-validated re-ingest gate),
                    flywheel_rules (the poisoning-interlock HealthRules)
  loop.py           FleetClient (episode driver + outcome closer) and
                    FlywheelLoop (the closed cycle end to end)

tests/test_flywheel.py walks the gate, the closed loop and the
stale-params control (the staleness ceiling must breach when no export
reaches the fleet).
"""

from tensor2robot_tpu.flywheel.capture import (  # noqa: F401
    EpisodeRecorder,
    FlywheelIngest,
    IngestRejected,
    ServedRecord,
    flywheel_rules,
)
from tensor2robot_tpu.flywheel.loop import (  # noqa: F401
    FleetClient,
    FlywheelConfig,
    FlywheelLoop,
)
