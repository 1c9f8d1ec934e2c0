"""The share of the device time of the whole programs of `kinds` in the
traced span that the ops under one scope took: 100 x (self time of the ops
whose `op_name` path matches the regex `scope`, less those whose opcode in
the display name is `not_op`) / (the programs' summed device time).
`chipbench/scoped.py` makes the table from the program's own
`program_scopes` and logs its roll-up once a run; a kind this process
pairs no programs of (`block` outside runners/engine_diffusion.py) or that
has no whole program in the span is skipped, and a program that cannot say
(a parent commit) gives None."""

from chipbench import scoped


def read(ctx, kinds, scope: str, not_op: str = None):
    table = scoped.table(ctx, kinds)
    if table is None or table.device_ns <= 0:
        return None
    return 100.0 * scoped.matching_ns(table, scope, not_op) / table.device_ns
