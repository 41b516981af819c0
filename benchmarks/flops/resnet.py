"""MACs of ResNet (bottleneck, shortcut type B) from its shapes alone."""

STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def conv_layers(depth=50, image=224, classes=1000):
    """[(name, c_in, c_out, kernel, stride, h_out)] in forward order, then
    the classifier as a 1x1 'conv' on a 1x1 map."""
    out = []
    h = image // 2
    out.append(("conv1", 3, 64, 7, 2, h))
    h //= 2                                   # 3x3/2 max pool
    c_in = 64
    for si, count in enumerate(STAGES[depth]):
        n = 64 * 2 ** si
        for bi in range(count):
            stride = 2 if (bi == 0 and si > 0) else 1
            h_out = h // stride
            out.append((f"s{si}b{bi}.c1", c_in, n, 1, 1, h))
            out.append((f"s{si}b{bi}.c2", n, n, 3, stride, h_out))
            out.append((f"s{si}b{bi}.c3", n, 4 * n, 1, 1, h_out))
            if c_in != 4 * n:
                out.append((f"s{si}b{bi}.sc", c_in, 4 * n, 1, stride, h_out))
            c_in, h = 4 * n, h_out
    out.append(("fc", c_in, classes, 1, 1, 1))
    return out


def forward_macs(depth=50, image=224, classes=1000):
    return sum(ci * co * k * k * h * h
               for _, ci, co, k, _, h in conv_layers(depth, image, classes))


def train_flops_per_image(depth=50, image=224, classes=1000):
    """2 x MACs, forward + backward (= 3 x forward)."""
    return 3 * 2 * forward_macs(depth, image, classes)
