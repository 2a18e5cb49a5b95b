"""Entry points: the serving launcher (`python -m repro.launch.serve`) and
the persistent compilation cache it and the chip benchmark switch on."""
