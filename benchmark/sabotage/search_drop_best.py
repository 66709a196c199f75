"""Sabotage `search_drop_best` (rehearsal only, for benchmark/tests):
the search daemon commits every answer without its best hit — the
timed path broken where an answer is produced."""


def apply() -> None:
    from libsplinter_tpu.engine.searcher import Searcher
    hits = Searcher._commit_hits

    def bad_hits(self, r, scores, idxs, k_fetch):
        return hits(self, r, scores[1:], idxs[1:], k_fetch)
    Searcher._commit_hits = bad_hits
