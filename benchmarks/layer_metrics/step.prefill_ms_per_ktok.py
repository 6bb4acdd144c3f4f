"""Step programs: device time of the programs that prefill in the traced
slice — the ragged joins, and the prologue prefill where the batch was
empty — over thousands of prompt tokens the ragged joins prefilled there
(segment_prefill_tokens, by difference). A ragged program also decodes
one token for every live row, so its time is in
step.decode_ms_per_token too; the two do not add up."""


def read(ctx):
    trace, sl = ctx["trace"], ctx["slice"]
    if not trace or not sl:
        return None
    names = ctx["names"]["programs"]
    seconds = sum(s for n, s in trace["module_seconds"].items()
                  if any(p in n for p in names["ragged"]))
    tokens = (sl["counters_end"]["scheduler"]["segment_prefill_tokens"]
              - sl["counters_start"]["scheduler"]["segment_prefill_tokens"])
    return 1e6 * seconds / tokens if tokens > 0 and seconds > 0 else None
