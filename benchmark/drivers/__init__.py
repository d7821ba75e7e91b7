"""One driver per configuration ``kind``; ``run.py`` picks by that key."""
