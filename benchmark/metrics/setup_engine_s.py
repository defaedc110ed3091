"""The engine start's share of set-up (``setup_stages_s.engine`` of the
child's ``ready`` message): the KV pool, the warm-up's export-cache
loads or lowerings, the XLA compilation or cache load of every serving
program, the smoke pass and the step calibration."""


def read(run):
    return ((run.get("ready") or {}).get("stages_s") or {}).get("engine")
