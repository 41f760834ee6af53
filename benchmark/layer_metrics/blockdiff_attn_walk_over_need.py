"""Query-key pairs in the kernel blocks that the block-diffusion attention
walks over the pairs its mask leaves: the program's gauges
``attention.blockdiff.pairs_visited`` / ``pairs_needed``, set from the
shapes when the op is traced (1.249 at 4096 clean positions, blocks of 4
positions and kernel blocks of 512: 80 block pairs of which the 8 noisy
ones and the 16 on the clean diagonals are partly empty). 1 would be a
walk that visits nothing the mask empties."""


def read(run):
    gauges = run.get('gauges') or {}
    need = gauges.get('attention.blockdiff.pairs_needed')
    seen = gauges.get('attention.blockdiff.pairs_visited')
    if not need or not seen:
        return None
    return float(seen) / float(need)
