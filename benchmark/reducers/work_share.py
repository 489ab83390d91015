"""Share (%) of the chip's peak that the window's required work took:
``work[flops]`` over ``work[seconds] x peak FLOP/s x chips``.  The
FLOPs are what the model requires (the configuration's program
module counts them, ``programs/<program>.py``), so this is a
model-FLOPs utilisation."""


def reduce(metric, readings):
    w, peaks = readings["work"], readings["peaks"]
    flops, secs = w.get(metric["flops"]), w.get(metric["seconds"])
    if not flops or not secs or peaks is None:
        return None
    chips = readings["trace"]["devices"] if readings.get("trace") else 1
    return 100.0 * flops / (secs * peaks["flops"] * chips)
