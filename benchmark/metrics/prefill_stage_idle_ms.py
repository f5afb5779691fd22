"""Engine: device idle time inside the ``llm.step.prefill`` stage (its launch, its wait for the
first tokens and the rest of it, ``state_insert`` included) per admitting step of the traced
stretch. The idle gaps are the device trace's, the stage edges the flight log's, and the two
clocks are set against each other by the dispatches both record (``ray_tpu/util/profiling.summarize``)."""

from benchmark import scopes


def read(obs):
    return scopes.prefill_stage_idle_ms(obs)
