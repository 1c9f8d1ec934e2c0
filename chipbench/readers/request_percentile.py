"""A percentile over the counted requests' timeline field (ttft_ms,
tpot_ms, late_ms). Requests without a sample (unfinished, refused) are
failures and are counted in `failed`, not here."""

from chipbench import stats


def read(ctx, field: str, q: float):
    return stats.percentile(stats.field_values(ctx["records"], field), q)
