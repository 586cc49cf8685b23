"""Serving kernels: the fused sampling epilogue's share of its roofline. It
reads every slot's row of logits once when the whole batch is greedy and three
times when any row samples (``flops.fused_sample``); kernel seconds from the
device trace. Reads nothing where the epilogue is off."""
import flops
import readers


def read(obs):
    if obs.engine_info.get("epilogue") != "on":
        return None
    vocab, slots = obs.cell.model_fields["vocab_size"], obs.engine_info["slots"]
    # no name of its own in the trace: the custom call that takes every slot's row of logits
    operand = f"f32[{slots},1,{vocab}]"

    def match(name):
        return " custom-call(" in name and operand in name

    def work(rows):
        greedy = all(r.spec["temperature"] <= 0.0 for r, _ in rows)
        return flops.fused_sample(vocab, slots, 1 if greedy else 3)

    return readers.kernel_roofline(obs, match, work)
