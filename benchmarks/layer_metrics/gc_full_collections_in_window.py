"""Full collections (generation 2: the whole heap of the server's
process is walked, every thread waits) that ended in the window: the
rise of ``py_gc_collections_total_gen2``, which the program's
``gc.callbacks`` hook adds to once a collection
(``kcp_tpu/obs/runtime.py``). ``gc_pause_pct_*`` says what share of the
window the collector took; this says how many of its long passes there
were, so the two together give the length of one.

Prints beside it the rises of the two younger generations' counters, of
``py_gc_collected_objects_total`` (the cyclic garbage actually found:
how much the program needs a collector at all) and of
``py_gc_uncollectable_total``. A program without the counters (the
parent of the PR that added them) gives None.

Whatever the program, one line says what the run's process (it holds
the server) reads of itself when the readers run, after the drain: the
thresholds in force (``gc.get_threshold()``), the interpreter's own
count of collections by generation since the process started
(``gc.get_stats()``, set-up included) and the process's peak resident
set (:func:`peak_rss_kb`) beside the resident set as it stands — what a
parent can be compared by."""

import gc
import os
import resource


def resident_now_kb():
    """The resident set as it stands (``/proc/self/statm``), or None."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE") // 1024
    except (OSError, ValueError, IndexError):
        return None


def peak_rss_kb():
    """(kB, where from): the peak resident set of this process —
    ``VmHWM`` of ``/proc/self/status``; where the machine's /proc does
    not carry it (the sealed machine with the chip does not),
    ``ru_maxrss`` of ``getrusage``, which there counts what the
    accelerator's runtime maps too (15 GB in every run, either side)."""
    try:
        with open("/proc/self/status") as f:
            for row in f:
                if row.startswith("VmHWM:"):
                    return int(row.split()[1]), "VmHWM"
    except (OSError, ValueError, IndexError):
        pass
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss, "ru_maxrss"


def read(ctx):
    stats = gc.get_stats()
    rss, source = peak_rss_kb()
    print(f"[layer] collector of the run's process: thresholds "
          f"{gc.get_threshold()}, collections since start by generation "
          f"{[g['collections'] for g in stats]}, collected "
          f"{[g['collected'] for g in stats]}, peak RSS {rss} kB "
          f"({source}), resident now {resident_now_kb()} kB", flush=True)
    reg = ctx["registry"]
    runs = "py_gc_collections_total_gen"
    if runs + "2" not in reg:
        return None
    print(f"[layer] collector in the window: {reg[runs + '0']:g} young, "
          f"{reg[runs + '1']:g} of generation 1, {reg[runs + '2']:g} full; "
          f"{reg['py_gc_collected_objects_total']:g} objects collected, "
          f"{reg['py_gc_uncollectable_total']:g} uncollectable", flush=True)
    return float(reg[runs + "2"])
