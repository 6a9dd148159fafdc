"""Host seconds of the index build the cell needs in set-up (the merged
index G_(X u Y) for the MI joins, G_Y for the service), up to
``block_until_ready``."""


def read(run):
    return run.driver.build_s
