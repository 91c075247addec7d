"""Paged latent attention inside the decode programs of a family with
ONE latent sub-layer a layer (`sizes["mla_sub_layers"]`): share of its
roofline, operations AND bytes (`mla_roofline.latent_decode`; at 128
heads the absorbed step does 242 operations a cached byte against the
chip's 240, so either side may set the least time). A program without
the kernel, a run without a trace or another family's sizes read as
nothing."""
from perfbench.harness import device, mla_roofline, roofline


def read(run):
    traced, sz = mla_roofline._traced(run), run.sizes
    if traced is None or not all(
            k in sz for k in ("kr", "rope", "H", "mla_sub_layers")):
        return None
    # positions attended and live slots per step, from the dispatches the
    # hooks saw in the traced stretch: a chunk's j-th step sees j more
    # positions per live slot (as `mla_roofline.roofline_pct` counts them)
    ctx = live = n = 0
    for pre, post, c, active, context in run.facts["decodes"]:
        if pre >= run.traced["t0"] and post <= run.traced["t1"]:
            ctx += sum(context + j * active for j in range(c))
            live += c * active
            n += c
    if not n:
        return None
    ops, nbytes = mla_roofline.latent_decode(ctx / n, live / n, sz["H"],
                                             sz["kr"], sz["rope"])
    sub_layers = sz["mla_sub_layers"]
    return roofline.share_pct(ops * sub_layers, nbytes * sub_layers,
                              traced[0] / traced[1],
                              device.peaks(run.device_kind))
