"""Build: host seconds around ``IndexSpec.build`` and the padded cluster
layout the service scans, ending in ``block_until_ready``."""


def read(rec):
    return rec.get("build_s")
