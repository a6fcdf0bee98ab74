"""Host wait on the staging prefetch (TokenStore + staged_batches): the
program's ``engine.staging_wait_s`` histogram over the stream's wall time
(the engine's ``encode_corpus_s``), summed over the traced verdicts.  A host
wait share, not device idle time."""


def read(ctx):
    stream = sum(t.get("encode_corpus_s", 0.0) for t in ctx.timings)
    if stream <= 0:
        return None
    return 100.0 * ctx.staging_wait_s / stream
