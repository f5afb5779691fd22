"""Kernels: attention under the learned index, its share of its roofline in the traced stretch's
prefills (``indexer_score_roofline``'s reader with another count and scope). The least time
attention over the CHOSEN positions can take for the prompts admitted in the stretch, in every
layer held (the family's ``indexed_prefill_least``: q and the output moved once, k and v once, a
score and a weighted sum in each of 32 heads for min(t + 1, 2,048) positions a query;
``pairs_chosen`` of the flight log's admitting steps), over the device time under the scope
``indexed.attend`` in the programs with ``prefill`` in their name. It counts the chosen work
whatever runs it: a masked pass that attends to every causal pair does mean(t) / 2,048 times the
least and reads at most that much under 100 (about 20 at 20,000 positions): that is the reading
meant, not a fault; a pass over gathered rows would be held to the same count."""

from benchmark.common import load_reader


def read(obs):
    return load_reader("indexer_score_roofline")(obs, least_name="indexed_prefill_least", counter="pairs_chosen", kinds=("indexed.attend",))
