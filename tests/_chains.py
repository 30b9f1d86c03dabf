"""The remediation-chain assertion that test_policy and test_slo_archive
share."""

import time

from fiber_tpu.telemetry import explain as explainmod
from fiber_tpu.telemetry.flightrec import FLIGHT
from fiber_tpu.telemetry.policy import POLICY


def assert_linked_chain(rule):
    """The flight ring holds the rule's whole chain: anomaly, at least
    one action and one verified outcome, each carrying the anomaly's
    id as ``cause_id``."""
    POLICY.poll(now=time.monotonic() + 60.0)  # force the verification
    chain = next(c for c in explainmod.policy_chains(FLIGHT.snapshot())
                 if c["anomaly"] is not None
                 and c["anomaly"].get("kind") == rule)
    assert chain["actions"] and chain["outcomes"], chain
    assert all(e.get("cause_id") == chain["cause_id"]
               for e in chain["actions"] + chain["outcomes"]), chain
    return chain
