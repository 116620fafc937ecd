"""Trainer loop, program counter: compilations the trainer's CompileWatcher
saw inside the window (its ``recompile`` events). 0 is the only healthy
value; one inside the window is counted here, never hidden."""


def read(run: dict):
    return sum(e.get("count", 1) for e in run["events"] if e.get("etype") == "recompile")
