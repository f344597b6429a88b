"""scan: the engine's ``scanTime`` a query, mean over the window. Named for what that timer covers
today: the upload of decoded tables to the device. Parquet decode happens outside it."""


def read(run):
    times = [r.engine["scanTime"] / 1e6 for r in run.records if "scanTime" in r.engine]
    return sum(times) / len(times) if times else None
