"""Step programs: how many times a prefill brought a held expert's matrices in for each held
expert that got a pair, over the window's admitting steps: ``moe_expert_fetches`` (a block in the
grouped matmul's loop, a run of an expert's blocks in its kernel; a mean over the expert layers,
summed over the step's prefill programs) over ``prefill_experts_hit`` (a mean over the expert
layers AND over the programs, so times the programs: one for each group of the step's
``prefill_dispatch_t``) of the program's flight log. 1.0 is the floor: every expert hit is read
once. The loop at one to two blocks an expert reads between 1 and 2; the kernel reads 1. A program
whose log lacks the field (the parent of PR 64, a description that does not reckon it): nothing to
read."""

from benchmark import flight


def read(obs):
    rows = [s for s in flight.admitting_steps(obs) if s.get("moe_expert_fetches") and s.get("prefill_experts_hit")]
    hit = sum(s["prefill_experts_hit"] * len(s.get("prefill_dispatch_t") or [0]) for s in rows)
    return sum(s["moe_expert_fetches"] for s in rows) / hit if hit else None
