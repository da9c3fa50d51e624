"""An MoE model's actor step against its roofline (%): the least time
its operations and bytes allow (``bench.flops_moe.actor_step``: the held
parameters' traffic, the experts' work from the traced iterations'
``moe.assignments.train``) over the device time of the actor-step
program in the trace.  Nothing to read (None) without the record's
``moe``."""
from bench import flops_moe, trace_reduce


def read(rec):
    moe = rec.get("moe")
    dev = trace_reduce.module_seconds(rec["trace"], r"actor_step")
    if not moe or not dev or not rec["traced_iters"]:
        return None
    pk, n = rec["peaks"], len(rec["traced_iters"])
    per_iter = moe["assignments"]["traced"].get("train", 0) / n
    least = 0.0
    for it in rec["traced_iters"]:
        f, b = flops_moe.actor_step(moe["cfg"], rec["rows"],
                                    rec["prompt_len"] + it["gen_len"],
                                    it["gen_len"], per_iter)
        least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / dev
