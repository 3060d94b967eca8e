"""KV tokens read per scan step: the chunk program's ``reads_tokens``
(summed over layers, mean over KV heads) summed over lanes, averaged
over the window's steps."""


def read(run):
    c = run.get("counters")
    if c is None or not c["reads"].size:
        return None
    return float(c["reads"].sum()) / c["reads"].shape[0]
