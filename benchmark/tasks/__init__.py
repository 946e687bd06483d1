"""The tasks a configuration can train on, one file each (see
``image_classification.py`` for what a task gives)."""

# The task of a configuration file that names none: the image configurations
# predate the key, and a committed configuration file is never edited.
DEFAULT = "image_classification"
