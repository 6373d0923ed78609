"""Device idle share of the batch window: 1 - (union of device-op
intervals) / (traced window), in percent."""


def read(run):
    share = run.summary["idle_share"]
    return None if share is None else 100.0 * share
