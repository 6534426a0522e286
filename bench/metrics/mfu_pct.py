"""The whole serving step's share of the chip's peak: the encoder's
forward FLOPs (stem, blocks, head) of every frame launched in the
traced window, over the window times the peak."""
import yardstick as ys


def read(run):
    tr = run.trace
    frames = run.frames_launched()
    if tr is None or not frames or tr["window_s"] <= 0:
        return None
    pk = ys.peaks(run.device_kind)
    return (100.0 * frames * ys.encoder_flops(run.enc)
            / (tr["window_s"] * pk["flops_per_s"]))
