"""Useful over evaluated non-bonded pairs (%).

The numerator counts the atom pairs within ``r_cut`` in the window's
final state, each pair once, with the benchmark's own pair list; the
denominator is the slot pairs the NB kernel evaluates per step
(``pair_stats()["evaluated_slot_pairs"]``, per domain) summed over all
domains.  The numerator is the same whatever schedule evaluates it, so
the ratio shows the padding that a tighter pair scheme would remove.
"""


def read(ctx):
    evaluated = ctx.pair_stats.get("evaluated_slot_pairs")
    if not evaluated:
        return None
    i, _j = ctx.ref.pair_list(ctx.final_pos, ctx.box,
                              float(ctx.config["r_cut"]))
    return 100.0 * i.shape[0] / (evaluated * ctx.n_domains)
