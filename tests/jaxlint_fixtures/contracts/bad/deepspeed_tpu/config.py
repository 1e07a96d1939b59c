from . import constants as C


def keys():
    # references the key but not its schema default (JL104: the key
    # is read somewhere without the default constant)
    return [C.TIMEOUT]
