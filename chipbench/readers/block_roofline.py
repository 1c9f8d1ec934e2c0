"""A share of a roofline inside the programs of one kind that ran wholly
in the traced seconds, for a model that generates by diffusion over blocks
with a sparse-expert FFN (chipbench/sdar_work.py counts the work from the
paired `engine.dispatch` records; chipbench/paired.py pairs them, with the
`block` kind that runners/engine_diffusion.py adds to its table):

    what="program"  the least time to read what the programs' forward
                    passes must (bytes over the HBM peak) over the
                    programs' own device time
    what="gmm"      the grouped matmuls' least time for the real
                    assignments on the touched experts, at the expert's
                    width, over the kernel's self time in those programs
    what="attn"     the block step's attention: the live rows' context
                    keys and values once a pass and layer over the
                    paged-decode kernel's self time in those programs
                    (`_decode_call` events INSIDE `jit_run_block(`
                    programs: that is how they are told from a decode
                    step's in another cell)

Records without the block or `moe_*` fields give None; needed work counts
real rows and touched experts only, so a reading over 100% is a bug in the
count."""

from chipbench import flops, paired, sdar_work


def read(ctx, kind: str, what: str, op_pattern: str = None):
    if not ctx["peaks"]:
        return None
    whole = paired.whole_programs(ctx, kind, f"{kind} {what} roofline")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole
             if r.get("moe_assignments") is not None
             and (kind != "block" or r.get("block_passes"))]
    if not whole:
        ctx["log"](f"ring engine.dispatch: no {kind} record carries what "
                   f"the {what} roofline counts")
        return None
    pub, log = ctx["cell"].config, ctx["log"]
    block = pub["generation"]["block_length"]
    assignments = sum(r["moe_assignments"] for _, r in whole)
    touched = sum(r["moe_experts_touched"] for _, r in whole)
    if what == "gmm":
        need = sdar_work.gmm_work(pub, assignments, touched)
    else:
        nbytes = 0.0
        for _, r in whole:
            passes = r.get("block_passes") or 1
            ctx_tokens = sum(c for _, _, c in r["rows"])
            if what == "attn":
                nbytes += sdar_work.block_attn_bytes(
                    pub, passes, ctx_tokens, len(r["rows"]), block)
            else:
                nbytes += sdar_work.forward_bytes(
                    pub, passes, r["moe_experts_touched"], ctx_tokens,
                    sum(q for _, q, _ in r["rows"]))
        need = {"ops": 0.0, "bytes": nbytes}
    roof = flops.roofline_seconds(need, ctx["peaks"])
    if what == "program":
        took_ns = sum(e[2] for e, _ in whole)
    else:
        took_ns = paired.op_self_ns(ctx, whole, op_pattern)
    if took_ns <= 0:
        return None
    log(f"{kind} {what}: {len(whole)} programs paired with records, "
        f"{sum(len(r['rows']) for _, r in whole) / len(whole):.1f} live "
        f"rows a program, {assignments} real assignments, {touched} experts "
        f"touched; took {took_ns / 1e6:.3f} ms, least "
        f"{roof['seconds'] * 1e3:.3f} ms, {roof['bound']}-bound")
    return 100.0 * roof["seconds"] / (took_ns / 1e9)
