"""Generator kinds: one module per ``kind`` of a traffic file. A kind
exposes ``prepare(session, spec) -> plan`` (pure in the seed) and
``run(session, plan, spec, t_start) -> dict``; it reaches the server only
through ``benchmarks.loadgen.Session``."""
