"""The plain reference replay holds few rows of P at a time."""

import bench_tiny

from bench import harness, reference, spec, traffic

ROWS_AT_THE_FOLD = 6


def test_the_replay_holds_six_rows_at_the_fold(monkeypatch):
    """Rows of P live while a learner's row is folded, read from ``jax.live_arrays``.

    Each arithmetic operation of the fold is followed by a count of the
    bytes of every array made since the replay began, in rows of P.
    """
    import jax
    from jax._src.array import ArrayImpl

    w = bench_tiny.workload(bench_tiny.pick(family="dense_lm"))
    earlier = {id(a) for a in jax.live_arrays()}
    fam = spec.family(w.family)
    source = traffic.make(w.config, w.traffic, 3)
    params0 = harness.init_params(fam, fam.program_model(w.config), w.config, w.traffic,
                                  3, source)
    row_bytes = 4 * sum(leaf.size for leaf in jax.tree_util.tree_leaves(params0))
    seen, folding = [], []

    def count():
        if folding:
            live = [a for a in jax.live_arrays() if id(a) not in earlier]
            seen.append(sum(a.nbytes for a in live) / row_bytes)

    for op in ("__add__", "__sub__", "__mul__", "__rmul__", "__truediv__"):
        def counted(self, other, op=getattr(ArrayImpl, op)):
            out = op(self, other)
            count()
            return out

        monkeypatch.setattr(ArrayImpl, op, counted)

    rule = spec.part("rules", spec.federation(w.traffic)["aggregation_rule"]).Fold

    class Counted(rule):
        def add(self, row, weight):
            folding.append(True)
            count()
            super().add(row, weight)
            count()
            folding.pop()

    rounds = int(w.traffic["reference_rounds"])
    got = reference.run(spec.reference(w.family), w.config, w.traffic, params0, source,
                        rounds, fold=Counted)
    assert len(got.changes) == rounds
    assert len(seen) >= 4 * int(w.traffic["learners"]) * rounds
    # Whole rows; the losses and the batches are a few bytes more.  At least
    # the initial model, the global row and the learner's row are live.
    assert 3 <= max(seen) <= ROWS_AT_THE_FOLD + 0.01, max(seen)
