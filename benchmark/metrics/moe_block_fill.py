"""Step programs: the share of the grouped expert matmul's rows that hold a (token, expert) pair,
over the window's admitting steps: ``prefill_moe_pairs_local`` over ``moe_rows_computed`` of the
program's flight log (both means over the expert layers of the step's prefills). The rest of the
rows are the padding of each expert's run to whole blocks. A program whose log lacks the fields
(the parent of PR 34, a model without routed experts): nothing to read."""

from benchmark import flight


def read(obs):
    rows = [s for s in flight.admitting_steps(obs) if s.get("moe_rows_computed")]
    computed = sum(s["moe_rows_computed"] for s in rows)
    return 100.0 * sum(s["prefill_moe_pairs_local"] for s in rows) / computed if computed else None
