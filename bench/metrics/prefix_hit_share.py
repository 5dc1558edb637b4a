"""Share of the prompt tokens admitted in the window that the pool's
prefix trie (``PagePool.match_prefix``) served from shared pages, %."""


def read(run):
    adm = [r for r in run.served if r.admitted and r.due < run.seconds]
    total = sum(r.prompt_len for r in adm)
    if total == 0:
        return None
    return 100.0 * sum(r.matched for r in adm) / total
