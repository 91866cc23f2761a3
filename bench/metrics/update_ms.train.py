"""Device time per step of the trainer's update executable (``jit_update``:
gather of the table rows, loss gradient, scatter-add, optimizer), less
any CWS kernel time inside it (the data-parallel step featurizes inside
its update), averaged over the chips."""
from bench import kernels, xtrace


def read(layer):
    steps = layer.quantities.get("steps", 0)
    if not steps or layer.trace is None:
        return None
    per_dev = []
    for dev in layer.trace.devices:
        upd = xtrace.clip([(s, e) for n, s, e in dev.modules
                           if n.startswith("jit_update")], layer.lo, layer.hi)
        if not upd:
            return None
        kern = [(s, e) for n, s, e, h in dev.ops
                if kernels.is_cws_encode(n, h)]
        per_dev.append(xtrace.subtract(upd, kern))
    return sum(per_dev) / len(per_dev) / steps * 1e3
