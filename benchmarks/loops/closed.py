"""``"loop": "closed"``: each of the cell's ``streams`` clients sends its next query when the one before
has answered. One stream is the power run, and what exists; a cell that asks for more needs a loop of
its own beside this file."""

from benchmarks.harness import window


def run(cell: dict, calls: list, seconds: float, fact_rows: dict, probe, around) -> window.Window:
    if cell["streams"] != 1:
        raise ValueError(f"{cell['name']}: the closed loop drives one stream, the cell asks for {cell['streams']}")
    return window.run_window(calls, seconds, fact_rows, probe, around)
