"""mfu (%): model FLOPs of the rounds completed in the traced window over
the window, the chips and the chip's peak.  The FLOPs are the SA-Net
convolutions a training step needs (fedbench.counts), every site, every
local step; the peak is the bf16 one from peaks.json.  Layer: round step."""


def read(r):
    if r.rounds == 0:
        return None
    peak = r.peaks["bf16_flops_per_s"]
    return 100.0 * r.rounds * r.round_flops / (r.window_s * r.chips * peak)
