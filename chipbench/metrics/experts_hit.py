"""Distinct experts a MoE layer reads in one decode launch, the mean over
the MoE layers and the decode launches: the engine's expert-load counter,
kept on the device in the decode state and pulled once, after the window
(``DecodeEngine.expert_load``). It counts every launch since the engine's
state was made, the set-up's warm launches too. Nothing where the engine
keeps no such counter."""


def read(r):
    load = getattr(r.cell.eng, "expert_load", None)
    load = load() if load is not None else None
    if not load or not load["launches"]:
        return None
    return load["experts_hit"]
