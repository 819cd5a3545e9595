"""The plain reference of the step loop, as test code.

The engine runs one decode program ahead of the device wherever the scheduler
has nothing to do. What it must stay token-identical to is the *drained*
sequence — read the step in flight back, admit, advance prefills, verify where
speculation is on, dispatch one decode and read it in the same step — run on
every step. That is this policy: passed through ``PagedServingEngine``'s
``policy=`` argument, never registered, never shipped.
"""

from neuronx_distributed_llama3_2_tpu.serving.policy import (
    ActionType,
    StepAction,
    StepPolicy,
)

#: ids of the two legs a parity test is parametrised over
LOOPS = ("drained", "lookahead")


class DrainedPolicy(StepPolicy):
    """Every step is a step with a scheduler event."""

    name = "drained-reference"

    def actions(self, view):
        yield StepAction(ActionType.READBACK)
        yield StepAction(ActionType.ADMIT)
        if view.config.fused_step and view.prefilling_lanes:
            yield StepAction(ActionType.MIXED_DISPATCH)
            if view.last_mixed_dispatched:
                return
        else:
            yield StepAction(ActionType.PREFILL_CHUNK)
        if view.spec_enabled and view.degrade_level < 1:
            yield StepAction(ActionType.VERIFY)
            if view.last_verify_drafted:
                return
        yield StepAction(ActionType.DECODE_DISPATCH, mode="sync")


def loop_policy(loop: str):
    """The ``policy=`` argument of one leg: the reference for ``"drained"``,
    the engine's own for ``"lookahead"``."""
    assert loop in LOOPS, loop
    return DrainedPolicy() if loop == "drained" else None
