"""Mean body bytes of a read response in the window: the rise of
``read_response_bytes_total`` over the rise of the four
``read_requests_total_<verb>`` (kcp_tpu/server/handler.py ``_read``);
beside it the items the store returned to list calls
(``store_list_returned_total``, which the syncers' own lists feed too).
A program without the counters reads nothing."""

from benchmarks.layer_metrics.loop_ms_per_read import VERBS


def read(ctx):
    reg = ctx["registry"]
    if "read_response_bytes_total" not in reg:
        return None
    n = sum(reg.get(f"read_requests_total_{v}", 0.0) for v in VERBS)
    if n <= 0:
        return None
    print(f"[layer] read responses: {reg['read_response_bytes_total']:g} "
          f"bytes over {n:g} requests, "
          f"{reg.get('store_list_returned_total', 0.0):g} items returned by "
          f"the store's lists, in the window", flush=True)
    return reg["read_response_bytes_total"] / n
