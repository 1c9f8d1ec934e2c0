"""What the block-sparse layers read of their context in decode: the keys
they attended (`sparse_tokens_read` of the window's decode
`engine.dispatch` records: real rows, summed over sparse layers, kv-head
groups and fused steps) over what dense layers would have read for the
same rows (each row's context tokens, growing by one a fused step, times
`sparse_layers` times the kv heads). 100 would mean the selection is
bypassed. Records without the field (another model, a commit before the
sparse layers) give None."""

from chipbench import ring


def read(ctx):
    recs = ring.in_window(ctx, "engine.dispatch", "dispatch_ns")
    if not recs:
        return None
    recs = [r for r in recs if r["kind"] == "decode"
            and r.get("sparse_tokens_read") is not None]
    if not recs:
        return None
    groups = ctx["cell"].config["num_key_value_heads"]
    read_ = sum(r["sparse_tokens_read"] for r in recs)
    dense = sum(r["sparse_layers"] * groups
                * sum(c + j for _, _, c in r["rows"] for j in range(r["k"]))
                for r in recs)
    if dense <= 0:
        return None
    ctx["log"](f"sparse kept: {len(recs)} decode records in the window, "
               f"{read_} keys attended of {dense} in the rows' contexts")
    return 100.0 * read_ / dense
