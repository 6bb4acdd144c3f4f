"""Step programs: device time of the decode, ragged and verify programs
in the traced slice over the tokens they emitted there (the scheduler's
segment_decode_tokens, by difference over the slice)."""


def read(ctx):
    trace, sl = ctx["trace"], ctx["slice"]
    if not trace or not sl:
        return None
    names = ctx["names"]["programs"]
    pats = names["decode"] + names["ragged"] + names["verify"]
    seconds = sum(s for n, s in trace["module_seconds"].items()
                  if any(p in n for p in pats))
    tokens = (sl["counters_end"]["scheduler"]["segment_decode_tokens"]
              - sl["counters_start"]["scheduler"]["segment_decode_tokens"])
    return 1e3 * seconds / tokens if tokens > 0 and seconds > 0 else None
