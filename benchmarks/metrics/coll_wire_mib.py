"""Wire bytes per chip per step of the compiled step's collectives
(`utils/hlo_comm` on the chip-compiled HLO): a count, never a time."""

UNIT = "MiB/step"
BETTER = "lower"
LAYER = "collectives"
MOVES = "tokens_per_s_chip"
SOURCE = "program_counter"


def read(ctx):
    report = ctx.host.get("comm_report")
    if report is None or ctx.cell.chips < 2:
        return None
    return float(report()["total_wire_bytes"]) / 2**20
