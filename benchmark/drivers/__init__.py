"""One driver per configuration ``kind``: ``drivers/<kind>.py`` holds a
class ``Driver``, which ``manifest.py`` finds by that key."""
