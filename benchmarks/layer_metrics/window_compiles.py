"""Engine and compile cache: programs compiled or loaded inside the window.
The larger of two readings between the window's scrapes:
``quorum_tpu_recompiles_total`` and the compile-cache hit and miss lines in
the server's log. Expected 0: a program variant met first inside the window
stalls every resident row while it loads."""


def read(art):
    by_counter = (art["m1"].get("quorum_tpu_recompiles_total", 0.0)
                  - art["m0"].get("quorum_tpu_recompiles_total", 0.0))
    by_log = art["log_compiles1"] - art["log_compiles0"]
    return float(max(by_counter, by_log))
