"""Programs compiled or loaded from the persistent cache while the window
ran (engine loop). Set-up warms every program the window uses, so a sound
run reads 0; a shape the warm pass did not reach shows here."""


def read(run):
    return run.compiles.programs
