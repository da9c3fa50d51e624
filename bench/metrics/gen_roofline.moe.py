"""An MoE model's single-wave generation against its roofline (%): the
least time of the prefill and of each decode step, every held weight
read once a step with the k/v cache and the conv states
(``bench.flops_moe.generate``; the experts' work from the traced
iterations' ``moe.assignments.gen``), over the device time of the
generate program in the trace.  Nothing to read (None) without the
record's ``moe``."""
from bench import flops_moe, trace_reduce


def read(rec):
    moe = rec.get("moe")
    dev = trace_reduce.module_seconds(rec["trace"], r"generate")
    if not moe or not dev or not rec["traced_iters"]:
        return None
    pk, n = rec["peaks"], len(rec["traced_iters"])
    per_iter = moe["assignments"]["traced"].get("gen", 0) / n
    phases = flops_moe.generate(moe["cfg"], rec["rows"], rec["prompt_len"],
                                rec["response_len"] - 1, per_iter)
    least = sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
                for f, b in phases)
    return 100.0 * n * least / dev
