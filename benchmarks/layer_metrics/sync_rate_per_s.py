"""The plain syncer's bulk rate: four fifths of the residents over the
time between the instant by which a tenth of them were seen and the
instant by which nine tenths were (40,000 / (t90 - t10) at 50,000). The
middle of the sync, so neither the first syncer's start nor the last
stragglers are in it, and no window length pins it. Residents never
seen count at the deadline."""

from benchmarks import sync_times


def read(ctx):
    got = sync_times.residents(ctx)
    if got is None:
        return None
    _due, took, _unseen = got
    n = len(took)
    t10, t90 = took[max(0, n // 10 - 1)], took[max(0, n * 9 // 10 - 1)]
    if t90 <= t10:
        return None
    between = n * 9 // 10 - n // 10
    print(f"[layer] sync rate: {between} residents seen between {t10:.2f} s "
          f"and {t90:.2f} s after the registration", flush=True)
    return between / (t90 - t10)
