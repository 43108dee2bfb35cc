"""The whole step's share of the chip's bf16 peak: the model FLOPs the
driver counted for the window (``lib/counts.py``; recomputed work not
counted) over the window's wall time and the peak."""


def read(trace, facts, peaks):
    if not facts.get("model_flops") or not facts.get("window_s"):
        return None
    return 100.0 * facts["model_flops"] / facts["window_s"] / peaks[
        "bf16_flops"]
