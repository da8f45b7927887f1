"""Clusters the deployment splitter read for one lookup of a workspace's
placement-eligible clusters, in the window
(``splitter_cluster_candidates_total`` over
``splitter_cluster_lookups_total``, one add each a call of
``kcp_tpu/reconcilers/deployment/controller.py`` ``_clusters_for``: the
clusters handed to the evacuation filter, and the calls). Read from the
``Cluster`` informer's ``by_workspace`` bucket it is the workspace's
own clusters, 8 in ``splitter-125x8`` whatever the fleet's size; a scan
of every registered cluster would read 1,000 there. It describes what a
lookup costs, the section ``kcp.splitter.clusters`` of
``loop_ms_per_write`` says what the lookups cost together; a program
without the counters (the parent of the PR that added them) reads
nothing."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "splitter_cluster_candidates_total",
                             "splitter_cluster_lookups_total")
