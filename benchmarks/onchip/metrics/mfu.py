"""mfu (%): model FLOPs of the rounds completed in the traced window over
the window, the chips and the chip's peak.  The FLOPs of one round are
the configuration's model family's count (``round_flops`` of
``families/<family>.py``: every site, every local step, forward and
backward, recomputation not counted); the peak is the bf16 one from
peaks.json.  Layer: round step."""


def read(r):
    if r.rounds == 0:
        return None
    peak = r.peaks["bf16_flops_per_s"]
    return 100.0 * r.rounds * r.round_flops / (r.window_s * r.chips * peak)
