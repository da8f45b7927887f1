"""Mean of ``cluster_syncer_start_seconds`` over the window
(reconcilers/cluster/controller.py: a ``Cluster`` with no syncer first
taken by a worker -> its syncer started and Ready written; importer,
negotiation and the syncer's initial lists included). Prints
``cluster_syncer_restarts_total``' rise beside it: syncers that were
started, stopped and started again."""

from benchmarks import phase_means


def read(ctx):
    print(f"[layer] syncer restarts in the window: "
          f"{ctx['registry'].get('cluster_syncer_restarts_total', 0.0):g}",
          flush=True)
    return phase_means.mean_ms(ctx, "cluster_syncer_start_seconds")
