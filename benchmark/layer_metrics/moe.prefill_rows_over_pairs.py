"""How much an expert layer lays out in a prefill for what it computes:
the rows of the sorted pair list handed to the grouped matmuls (the
blocks the layer walked times a block; every pair of every token where
it laid them all out at once) over the pairs routed to the experts held
here, a mean over the engine's recent prefills and the expert layers. 1
is a layer whose gathers, activation and kernel grid follow the pairs
that land here; a layer that lays out all T x k pairs reads the inverse
of the share of the experts it holds (8 with 20 of 160). From
``Engine.stats()["moe"]``; nothing on a program without the counter.

The log line says how near the layers' loads come to a block: the pairs
routed here a row of the program, least, mean and most over the recent
prefills, a layer at a time (a block holds a third more than the mean a
balanced router gives)."""


def read(obs):
    moe = obs.get("counters", {}).get("moe")
    if not moe or moe.get("prefill_rows_over_pairs") is None:
        return None
    prefills = [c for c in moe["calls"] if not c[2]]
    by_layer = zip(*([pairs / max(rows_run, 1) for pairs in layers]
                     for _, rows_run, _, layers, _ in prefills))
    obs["log"]("moe.prefill_rows_over_pairs: %d prefills; pairs a row, "
               "least / mean / most, by layer: %s"
               % (len(prefills), "; ".join(
                   "%.2f / %.2f / %.2f" % (min(v), sum(v) / len(v), max(v))
                   for v in by_layer)))
    return moe["prefill_rows_over_pairs"]
