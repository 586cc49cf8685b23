"""Serving kernels: the Pallas paged-decode attention kernel's share of its
roofline. Work per token step and layer from ``flops.paged_decode_attention``
(each row reads its K and V once), kernel seconds from the device trace. Reads
nothing where the engine decodes through the XLA gather."""
import flops
import readers


def read(obs):
    if obs.engine_info.get("decode_path") != "pallas":
        return None
    mc = obs.cell.model_fields
    hd = mc.get("head_dim") or mc["hidden_size"] // mc["num_heads"]
    # the kernel has no name of its own in the trace: it is the custom call of the
    # decode program whose result is one attention row per slot, [slots, heads, head_dim]
    out_shape = f"= bf16[{obs.engine_info['slots']},{mc['num_heads']},{hd}]"

    def match(name):
        return " custom-call(" in name and out_shape in name

    def work(rows):
        w = flops.paged_decode_attention(mc, [c for _, c in rows])
        return {k: v * mc["num_layers"] for k, v in w.items()}

    return readers.kernel_roofline(obs, match, work)
