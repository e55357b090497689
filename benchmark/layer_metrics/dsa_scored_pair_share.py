"""(query, key) pairs whose main-attention score the program FORMED over
the causal pairs, in the window, over all blocks
(`veles_dsa_pairs_scored_total` over `veles_dsa_pairs_causal_total`): 100
for a form that scores every causal pair and masks; the selected pairs'
share (23.4 % at 16,384 tokens and 2,048 keys) is the floor."""

from benchmark import keye2_scopes as K


def read(ctx):
    layers = K.dsa_counters()
    if not layers or not all(c["causal"] for c in layers.values()):
        return None
    return 100.0 * sum(c["scored"] for c in layers.values()) \
        / sum(c["causal"] for c in layers.values())
