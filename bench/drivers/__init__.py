"""One driver per traffic kind: ``bench/drivers/<kind>.py``."""
