"""Input pipeline: mean time the step loop waited for the host prefetcher's
next batch in the window (the program's ``pipe_step_wait_ms``)."""


def read(obs):
    return obs.train.get("pipe", {}).get("pipe_step_wait_ms")
