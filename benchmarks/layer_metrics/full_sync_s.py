"""Seconds from the registration instant (every ``Cluster`` create due)
to the LAST resident's status seen on the generator's watch: the time an
operator waits for a restarted control plane to be whole again. A
resident never seen counts at the deadline."""

from benchmarks import sync_times


def read(ctx):
    got = sync_times.residents(ctx)
    if got is None:
        return None
    _due, took, unseen = got
    print(f"[layer] full sync: {len(took)} residents, {unseen} never seen; "
          f"seconds after the registration fell due by which 1/10/50/90/99/"
          f"100 % were seen: "
          + ", ".join(f"{took[min(len(took) - 1, int(q * len(took)))]:.2f}"
                      for q in (0.01, 0.10, 0.50, 0.90, 0.99, 1.0)),
          flush=True)
    return took[-1]
