"""Process start to the first measured frame: import, backend start, native
build or load, app build, warm-up (compile or cache load), warm frames,
producers' pools. Host clock."""


def read(run: dict):
    return run["setup_s"]
