"""The widest slot vocabulary any syncer engine's encoder has grown, as
a share of the bucket's S (``encoder_slot_vocab_max`` / ``fleet.S``). At
100 % the next new field path overflows the bucket and its engine
re-registers at 2 S. A gauge: read as it stands when the run ends, not
as a rise over the window."""

from benchmarks import deploy


def read(ctx):
    widest = deploy.registry_snapshot().get("encoder_slot_vocab_max")
    slots = (ctx.get("fleet") or {}).get("S")
    if not widest or not slots:
        return None
    print(f"[layer] encoder_slot_vocab_max: {widest:g} slots of S={slots}",
          flush=True)
    return 100.0 * widest / slots
