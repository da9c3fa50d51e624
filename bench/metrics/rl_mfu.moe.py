"""Model FLOP utilisation of an MoE model's RL iterations (%): the model
operations of generation, the reference forward and the actor's forward
and backward, with the experts' work counted from the window's
``moe.assignments.gen`` and ``moe.assignments.train``
(``bench.flops_moe.rl_iteration_flops``), over window x chips x the
chip's bf16 peak.  Nothing to read (None) without the record's ``moe``."""
from bench import flops_moe


def read(rec):
    moe = rec.get("moe")
    if not moe or not rec["iters"]:
        return None
    cfg, n = moe["cfg"], len(rec["iters"])
    a = moe["assignments"]["window"]
    f = sum(flops_moe.rl_iteration_flops(
        cfg, rec["rows"], rec["prompt_len"], rec["rows"] * it["gen_len"],
        a.get("gen", 0) / n, a.get("train", 0) / n) for it in rec["iters"])
    return 100.0 * f / (rec["window_s"] * rec["chips"]
                        * rec["peaks"]["bf16_flops"])
