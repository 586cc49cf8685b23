"""Model step, train: required operations of the traced steps over what the
chip could do in the seconds it was busy (device-busy seconds x bf16 peak).
Required = ``flops.train_flops_per_token_lora`` x non-padding tokens;
recomputation under remat is not required work, so full remat caps this near 2/3."""
import trace_reduce


def read(obs):
    if obs.flat is None or not obs.train:
        return None
    lo, hi = obs.trace_clock
    busy = trace_reduce.busy_idle(obs.flat, lo, hi)["busy_s"]
    steps = obs.train["trace_steps"]
    need = obs.train["flops_per_token"] * obs.train["tokens_per_step"] * steps
    return 100.0 * need / (busy * obs.peaks["bf16_flops"])
