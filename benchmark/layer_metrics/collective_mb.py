"""Bytes the compiled step's collectives move per step, counted by
``benchmark/hlo_count.py`` over the HLO of the executable that ran (the
program's ``collective_profile`` leaves out tuple-shaped all-reduces). A
count: it repeats exactly. Nothing to read where the step has no collective."""
LAYER = "sharded step"
UNIT = "MB"
MOVES = "tokens_per_s_per_chip"


def reports(cell):
    return cell["chips"] > 1


def read(window):
    from benchmark import hlo_count

    moved = sum(hlo_count.collective_bytes(window.compiled_text).values())
    return moved / 1e6 if moved else None
